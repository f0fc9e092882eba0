"""Distribution: sharding rule system (unit), pipeline + sharded train step
(subprocess with 8 forced host devices — env must be set pre-jax-init)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import ShardingConfig, auto_spec, spec_for_axes


def test_spec_for_axes_rules():
    sh = ShardingConfig(fsdp=True, dp_axes=("data",))
    rules = sh.rules()
    assert spec_for_axes(("embed", "heads"), rules) == P("data", "model")
    assert spec_for_axes(("layers", "embed", "mlp"), rules) == P(None, "data", "model")
    assert spec_for_axes((None,), rules) == P(None)


def test_spec_no_duplicate_mesh_axes():
    sh = ShardingConfig(fsdp=False)
    rules = sh.rules()
    # two logical dims mapping to "model": only the first gets it
    assert spec_for_axes(("heads", "mlp"), rules) == P("model", None)


def test_auto_spec_divisibility(monkeypatch):
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.zeros((4, 8))

    sh = ShardingConfig(dp_axes=("data",))
    assert auto_spec((16, 64), FakeMesh(), sh, batch_dim=0) == P("data", "model")
    # batch not divisible by data=4 → dp moves to another divisible dim
    assert auto_spec((3, 64), FakeMesh(), sh, batch_dim=0)[0] is None
    # nothing divisible → fully replicated
    assert auto_spec((3, 5), FakeMesh(), sh, batch_dim=0) == P(None, None)


_SUBPROCESS_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
"""


def _run_sub(body: str) -> str:
    code = _SUBPROCESS_PRELUDE + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=Path(__file__).resolve().parents[1], timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pipeline_matches_sequential_subprocess():
    print(_run_sub("""
    from repro.dist.pipeline import pipeline_apply, sequential_reference
    mesh = jax.make_mesh((4, 2), ("stage", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    S, D = 4, 16
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, D, D)) * 0.3}
    def block(p, x):
        return jnp.tanh(x @ p["w"])
    x = jax.random.normal(jax.random.PRNGKey(1), (16, D))
    ref = sequential_reference(block, params, x)
    out = pipeline_apply(block, params, x, mesh, "stage", num_micro=4)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err
    print("pipeline ok", err)
    """))


def test_sharded_train_step_subprocess():
    """FSDP+TP train step on a tiny llama over a 2x4 mesh: runs, loss finite,
    and params stay correctly sharded."""
    print(_run_sub("""
    import dataclasses
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced_config
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import make_train_step, shardings_for_cell
    from repro.train.optimizer import OptConfig, adamw_init
    from repro.models import init_model
    from repro.dist.ctx import activation_sharding

    cfg = dataclasses.replace(
        reduced_config(get_arch("llama3.2-1b")),
        num_layers=2, d_model=32, d_ff=64, num_heads=4, num_kv_heads=2,
        head_dim=8, vocab_size=128,
    )
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeConfig("tiny", 16, 8, "train")
    sh = shardings_for_cell(cfg, shape, mesh)
    step = make_train_step(cfg, OptConfig(warmup_steps=1, stable_steps=10, decay_steps=1))
    with activation_sharding(mesh, sh["shcfg"]):
        jitted = jax.jit(step, in_shardings=(sh["params_sharding"], sh["opt_sharding"], sh["batch_sharding"]))
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, sh["params_sharding"])
        opt = jax.device_put(adamw_init(params), sh["opt_sharding"])
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jax.device_put(jnp.asarray(rng.integers(0, 128, (8, 16))), sh["batch_sharding"]["tokens"]),
            "labels": jax.device_put(jnp.asarray(rng.integers(0, 128, (8, 16))), sh["batch_sharding"]["labels"]),
        }
        p2, o2, m = jitted(params, opt, batch)
    loss = float(m["loss"])
    assert np.isfinite(loss), loss
    # a second step must also run (state shardings round-trip)
    p3, o3, m2 = jitted(p2, o2, batch)
    assert float(m2["loss"]) < loss + 1.0
    emb = p2["embed"]
    assert emb.sharding.spec == P("model", "data"), emb.sharding
    print("sharded train ok", loss, float(m2["loss"]))
    """))


def test_serve_step_sharded_subprocess():
    print(_run_sub("""
    import dataclasses
    from repro.configs import get_arch, reduced_config
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import make_serve_step, shardings_for_cell
    from repro.models import init_model, init_cache

    cfg = dataclasses.replace(
        reduced_config(get_arch("qwen2.5-3b")),
        num_layers=2, d_model=32, d_ff=64, num_heads=4, num_kv_heads=2,
        head_dim=8, vocab_size=128,
    )
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeConfig("tinydec", 64, 8, "decode")
    sh = shardings_for_cell(cfg, shape, mesh)
    step = make_serve_step(cfg)
    jitted = jax.jit(step, in_shardings=(sh["params_sharding"], sh["cache_sharding"], sh["token_sharding"]))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, sh["params_sharding"])
    cache = jax.device_put(init_cache(cfg, 8, 64), sh["cache_sharding"])
    tok = jax.device_put(jnp.ones((8, 1), jnp.int32), sh["token_sharding"])
    logits, cache2 = jitted(params, cache, tok)
    assert logits.shape == (8, 1, 128)
    assert bool(jnp.all(jnp.isfinite(logits)))
    print("sharded serve ok")
    """))
