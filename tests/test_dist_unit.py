"""Single-device unit coverage for `repro.dist`: `tree_shardings` over a
real `init_model` Param tree, `ashard` identity behaviour outside an
`activation_sharding` context, and the ZeRO-1 optimizer-state layout.

Runs on the one real CPU device (a 1×1 mesh) — the multi-device paths live
in `tests/test_dist.py` subprocesses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_arch, reduced_config
from repro.dist.ctx import activation_sharding, ashard
from repro.dist.sharding import (
    ShardingConfig,
    batch_specs,
    cache_specs,
    opt_state_specs,
    tree_shardings,
)
from repro.models import init_cache, init_model


def _tiny_cfg():
    return dataclasses.replace(
        reduced_config(get_arch("llama3.2-1b")),
        num_layers=2, d_model=32, d_ff=64, num_heads=4, num_kv_heads=2,
        head_dim=8, vocab_size=128,
    )


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _specs(sharding_tree):
    return [s.spec for s in jax.tree.leaves(sharding_tree)]


def test_tree_shardings_covers_every_param_leaf():
    cfg = _tiny_cfg()
    params, axes = init_model(jax.random.PRNGKey(0), cfg)
    mesh = _mesh11()
    sh = tree_shardings(axes, mesh, ShardingConfig(fsdp=True), shapes_tree=params)
    leaves = jax.tree.leaves(sh)
    assert leaves, "empty sharding tree"
    assert all(isinstance(s, NamedSharding) for s in leaves)
    # structure matches the param tree exactly
    assert jax.tree.structure(sh) == jax.tree.structure(params)
    # every spec has the rank of its param
    for s, p in zip(leaves, jax.tree.leaves(params)):
        assert len(s.spec) == p.ndim, (s.spec, p.shape)


def test_fsdp_toggle_differs_only_on_dp_axis():
    cfg = _tiny_cfg()
    params, axes = init_model(jax.random.PRNGKey(0), cfg)
    mesh = _mesh11()
    tp_only = _specs(tree_shardings(axes, mesh, ShardingConfig(fsdp=False), shapes_tree=params))
    fsdp = _specs(tree_shardings(axes, mesh, ShardingConfig(fsdp=True), shapes_tree=params))
    assert tp_only != fsdp  # fsdp actually shards something extra
    for spec_tp, spec_fsdp in zip(tp_only, fsdp):
        for entry_tp, entry_fsdp in zip(spec_tp, spec_fsdp):
            if entry_tp != entry_fsdp:
                # the only allowed difference: an embed dim picking up "data"
                assert entry_tp is None and entry_fsdp == "data", (spec_tp, spec_fsdp)


def test_ashard_is_identity_outside_context():
    x = jnp.arange(12.0).reshape(3, 4)
    assert ashard(x, "dp", "tp") is x
    assert ashard(x, None, None) is x


def test_ashard_constrains_inside_context():
    mesh = _mesh11()
    x = jnp.ones((4, 8))
    with activation_sharding(mesh, ShardingConfig()):
        y = jax.jit(lambda t: ashard(t, "dp", "tp") * 2.0)(x)
    np.testing.assert_allclose(np.asarray(y), 2.0 * np.asarray(x))
    # context popped cleanly — identity again
    assert ashard(x, "dp", "tp") is x


def test_opt_state_specs_zero1_matches_fsdp_layout():
    cfg = _tiny_cfg()
    params, axes = init_model(jax.random.PRNGKey(0), cfg)
    mesh = _mesh11()
    # serving-style TP-only params, but moments still take the FSDP layout
    moments = opt_state_specs(axes, mesh, ShardingConfig(fsdp=False), shapes_tree=params)
    fsdp = tree_shardings(axes, mesh, ShardingConfig(fsdp=True), shapes_tree=params)
    assert _specs(moments) == _specs(fsdp)


def test_batch_and_cache_specs_ranks():
    cfg = _tiny_cfg()
    mesh = _mesh11()
    shcfg = ShardingConfig(fsdp=False)
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32), "labels": jnp.zeros((8, 16), jnp.int32)}
    for name, spec in batch_specs(batch, mesh, shcfg).items():
        assert len(spec) == batch[name].ndim
    cache = init_cache(cfg, 8, 32)
    cspecs = cache_specs(cache, mesh, shcfg)
    for leaf, spec in zip(jax.tree.leaves(cache), jax.tree.leaves(cspecs)):
        assert len(spec) == leaf.ndim, (leaf.shape, spec)
