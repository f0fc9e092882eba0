"""Compiles of the main path for a described TPU v5e chip (``v5e:2x2``).

Nothing runs: the TPU compiler builds each program for a chip that is
described, not attached, which catches what the Pallas interpreter and the
CPU backend accept but the chip refuses — unaligned tiles, kernels that use
too much fast memory, programs that do not fit HBM, collectives that cannot
be partitioned.  Shapes are those of ``chip_smoke.py``: the ogbn-arxiv-class
graph (169,343 vertices + one scratch row) under the 3-layer GCN
[128, 256, 256, 40], with the capacity buckets its host planner produces.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import incremental
from repro.core.affected import (
    PackedLayout,
    ShardedLayout,
    layout_slices,
    sharded_layout_slices,
)
from repro.core.models import make_model
from repro.kernels.delta_agg import DELTA_BD, DELTA_BE, DELTA_TV, delta_agg

V5E_HBM_BYTES = 16 * 10**9
N = 169_343
DIMS = (128, 256, 256, 40)
CTX = 1  # GCN's neighbourhood context is the in-degree count
# per-layer (e, r, f, fe, o) capacities the planner reaches on the smoke's
# stream: by layer 3 the 3-hop frontier of 64 updates covers every vertex
SMOKE_CAPS = ((16384, 8192, 16, 16, 8192), (524288, 131072, 16, 16, 131072),
              (4194304, 262144, 16, 16, 262144))
SMOKE_PALLAS_CAPS = (131072, 2097152, 8388608)
SHARDED = ShardedLayout(
    n=N, n_shards=4, rows_per=42336, feat_cap=16,
    caps=((8192, 4096, 16, 16, 4096, 128, 42465),
          (262144, 32768, 16, 16, 32768, 8192, 50529),
          (2097152, 65536, 16, 16, 65536, 131072, 173409)),
    halo_mode="ppermute", pair_caps=(64, 2048, 32768))
SHARDED_PALLAS_CAPS = (65536, 1048576, 2097152)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture
def compiled_pallas(monkeypatch):
    """The step picks Pallas interpret mode from the default backend (the
    CPU here); a compile for the chip needs the compiled kernel."""
    monkeypatch.setattr(incremental, "_pallas_interpret", lambda: False)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding):
    model = make_model("gcn")
    shapes = jax.eval_shape(
        lambda: model.init_layers(jax.random.PRNGKey(0), list(DIMS)))
    return model, tuple(jax.tree.map(
        lambda a: _sds(sharding, a.shape, a.dtype), shapes))


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


@pytest.mark.parametrize("width", [128, 256])
def test_delta_agg_compiles_for_v5e(one_chip, width):
    e, rows = 4096, 2048
    compiled = delta_agg.lower(
        _sds(one_chip, (e, width)), _sds(one_chip, (e,), jnp.int32),
        _sds(one_chip, (e // DELTA_BE,), jnp.int32),
        _sds(one_chip, (rows, width)),
        tv=DELTA_TV, be=DELTA_BE, bd=DELTA_BD, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("scatter", ["xla", "pallas"])
def test_fused_stream_step_fits_one_v5e(one_chip, scatter, request):
    model, params = _params(one_chip)
    layout = PackedLayout(n=N, feat_cap=16, caps=SMOKE_CAPS)
    idx_len, flt_len, msk_len = layout_slices(layout)[3]
    rows = N + 1
    pallas = None
    if scatter == "pallas":
        request.getfixturevalue("compiled_pallas")
        pallas = tuple(
            (_sds(one_chip, (c,), jnp.int32), _sds(one_chip, (c,), jnp.int32),
             _sds(one_chip, (c // DELTA_BE,), jnp.int32))
            for c in SMOKE_PALLAS_CAPS)
    compiled = incremental.fused_stream_step.lower(
        model, layout, params,
        tuple(_sds(one_chip, (rows, d)) for d in DIMS),
        tuple(_sds(one_chip, (rows, d)) for d in DIMS[:-1]),
        tuple(_sds(one_chip, (rows, CTX)) for _ in DIMS[:-1]),
        _sds(one_chip, (idx_len,), jnp.int32), _sds(one_chip, (flt_len,)),
        _sds(one_chip, (msk_len,), jnp.bool_),
        _sds(one_chip, (layout.feat_cap, DIMS[0])), pallas,
    ).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (scatter == "pallas")


@pytest.mark.parametrize("scatter", ["xla", "pallas"])
def test_sharded_ppermute_step_compiles_for_v5e_2x2(mesh4, scatter, request):
    sh, rep = NamedSharding(mesh4, P("data")), NamedSharding(mesh4, P())
    model, params = _params(rep)
    layout = SHARDED
    pallas_sh = ()
    if scatter == "pallas":
        request.getfixturevalue("compiled_pallas")
        layout = ShardedLayout(**{**SHARDED.__dict__,
                                  "pallas_ecaps": SHARDED_PALLAS_CAPS})
        pallas_sh = tuple(
            (_sds(sh, (4, c), jnp.int32), _sds(sh, (4, c), jnp.int32),
             _sds(sh, (4, c // DELTA_BE), jnp.int32))
            for c in SHARDED_PALLAS_CAPS)
    idx_len, flt_len, msk_len, rep_len = sharded_layout_slices(layout)[4]
    rows = layout.rows_per + 1
    comms_sh = tuple(
        (_sds(sh, (4, 3, c), jnp.int32), _sds(sh, (4, 3, c), jnp.int32))
        for c in layout.pair_caps)
    step = incremental.sharded_step_fn(model, mesh4, "data")
    compiled = step.lower(
        layout, params,
        tuple(_sds(sh, (4, rows, d)) for d in DIMS),
        tuple(_sds(sh, (4, rows, d)) for d in DIMS[:-1]),
        tuple(_sds(sh, (4, rows, CTX)) for _ in DIMS[:-1]),
        _sds(sh, (4, idx_len), jnp.int32), _sds(sh, (4, flt_len)),
        _sds(sh, (4, msk_len), jnp.bool_), _sds(rep, (rep_len,), jnp.int32),
        _sds(rep, (layout.feat_cap,), jnp.bool_),
        _sds(rep, (layout.feat_cap, DIMS[0])), pallas_sh, comms_sh,
    ).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    assert ("tpu_custom_call" in text) == (scatter == "pallas")
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES
