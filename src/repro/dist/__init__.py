"""`repro.dist` — sharding, activation constraints, pipeline parallelism.

The distribution layer has three parts:

- `repro.dist.sharding` — logical-axis rules.  Params carry logical axis
  names (`nn/param.py`); `ShardingConfig.rules()` maps them to mesh axes,
  `tree_shardings` turns a whole param tree into `NamedSharding`s, and
  `auto_spec`/`batch_specs`/`cache_specs` cover inputs and decode caches.
- `repro.dist.ctx` — activation constraints.  Wrap execution in
  `activation_sharding(mesh, shcfg)` and every `ashard(x, "dp", "tp")`
  call inside the model becomes a `with_sharding_constraint`; outside the
  context `ashard` is an identity, so single-device runs are untouched.
- `repro.dist.pipeline` — `pipeline_apply`, microbatched GPipe-style
  pipelining over a mesh "stage" axis, with `sequential_reference` as the
  single-device oracle.

Usage::

    import jax
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.dist import activation_sharding
    from repro.launch.steps import make_train_step, shardings_for_cell

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = shardings_for_cell(cfg, ShapeConfig("tiny", 16, 8, "train"), mesh)
    with activation_sharding(mesh, sh["shcfg"]):
        step = jax.jit(make_train_step(cfg, opt_cfg),
                       in_shardings=(sh["params_sharding"],
                                     sh["opt_sharding"],
                                     sh["batch_sharding"]))
        params, opt, metrics = step(params, opt, batch)

The context only matters at trace time, and it is NOT part of jit's cache
key: re-entering it for later calls of an already-traced function is
unnecessary but harmless, while first-tracing a step *outside* the context
caches the unconstrained program for good (see `repro.dist.ctx`).  Enter
the context before the first call, as above.
"""
from repro.dist.ctx import activation_sharding, ashard
from repro.dist.pipeline import pipeline_apply, sequential_reference
from repro.dist.sharding import (
    ShardingConfig,
    auto_spec,
    batch_specs,
    cache_specs,
    opt_state_specs,
    spec_for_axes,
    stream_mesh,
    stream_state_specs,
    tree_shardings,
)

__all__ = [
    "ShardingConfig",
    "activation_sharding",
    "ashard",
    "auto_spec",
    "batch_specs",
    "cache_specs",
    "opt_state_specs",
    "pipeline_apply",
    "sequential_reference",
    "spec_for_axes",
    "stream_mesh",
    "stream_state_specs",
    "tree_shardings",
]
