"""Serving front-end (ISSUE 6): versioned snapshot reads, admission
control, the unified `create_engine` factory, and StreamStats as the
single result type.

The bitwise contract under test: a read pinned to version v returns rows
bitwise-equal to the serial post-batch-v state, no matter how many batches
ran between pin and service — on every backend, with async staging both on
and off for the host-resident pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    RTECEngine,
    ShardedRTECEngine,
    StreamStats,
    full_forward,
    make_model,
)
from repro.graph import make_graph, make_stream
from repro.graph.generators import random_features
from repro.serve import (
    BACKENDS,
    ChunkedRTECEngine,
    EngineConfig,
    ReadRejectedError,
    ServingFrontend,
    StaleVersionError,
    create_engine,
)
from repro.serve.offload import OffloadedRTECEngine, ShardedOffloadRTECEngine

TOL = 2e-4


def _mk_stream(n=150, num_batches=8, seed=0, feature_dim=8, batch_edges=8):
    g = make_graph("powerlaw", n, avg_degree=5, seed=seed, weighted=True)
    x, _ = random_features(n, 8, seed=seed)
    wl = make_stream(g, num_batches=num_batches, batch_edges=batch_edges,
                     delete_frac=0.35, seed=seed + 1,
                     feature_dim=feature_dim, feature_frac=0.02)
    return x, wl


def _cfg(model, wl, x, **kw) -> EngineConfig:
    params = model.init_layers(jax.random.PRNGKey(0), [8, 8])
    return EngineConfig(model=model, graph=wl.base, x=x, params=params, **kw)


def _serial_reference(backend, cfg, wl, rows):
    """Per-version row snapshots from an identically-constructed engine
    applying the stream serially: refs[v] is the post-batch-v state."""
    eng = create_engine(backend, cfg)
    refs = [np.array(eng.snapshot_rows(rows))]
    for b in wl.batches:
        eng.apply_batch(b)
        refs.append(np.array(eng.snapshot_rows(rows)))
    return refs


# ---------------------------------------------------------------------- #
# the tentpole contract: versioned reads are bitwise (ISSUE 6 acceptance)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("async_staging", [True, False])
@pytest.mark.parametrize("backend", ["offload", "sharded_offload"])
def test_versioned_reads_bitwise_offload_backends(backend, async_staging):
    """Deterministic read/write interleaving on the host-resident pair:
    after every batch, read *every* retained version v0..vk and require
    each bitwise-equal to the serial post-batch state — with the async
    staging worker both on and off."""
    model = make_model("gcn")
    x, wl = _mk_stream()
    cfg = _cfg(model, wl, x, async_staging=async_staging)
    rows = np.arange(0, wl.base.n, 5)
    refs = _serial_reference(backend, cfg, wl, rows)

    fr = ServingFrontend(create_engine(backend, cfg), max_pending_reads=256,
                         max_versions=len(wl.batches) + 1)
    for b in wl.batches:
        fr.apply_batch(b)
        for v in range(fr.version + 1):
            np.testing.assert_array_equal(fr.read(rows, version=v), refs[v])
    ss = fr.stats()
    # after batch i (version i+1) we read versions 0..i+1 → i+2 reads
    assert ss.reads_served == sum(i + 2 for i in range(len(wl.batches)))
    assert ss.reads_rejected == 0
    # each read() is its own serving round: one record per round, one
    # pinned group each, its gather and undo walk inside the round's time
    assert [r.reads for r in fr.read_rounds] == [1] * ss.reads_served
    assert all(r.groups == 1 and r.union_rows == rows.size
               and 0.0 < r.gather_s + r.undo_s <= r.seconds
               for r in fr.read_rounds)


@pytest.mark.parametrize("backend", BACKENDS)
def test_versioned_reads_bitwise_every_backend(backend):
    """All five substrates serve pinned reads bitwise-equal to the serial
    post-batch state (current version + two versions back)."""
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=6)
    cfg = _cfg(model, wl, x)
    rows = np.arange(0, wl.base.n, 7)
    refs = _serial_reference(backend, cfg, wl, rows)

    fr = ServingFrontend(create_engine(backend, cfg), max_versions=4)
    for b in wl.batches:
        fr.apply_batch(b)
        v = fr.version
        np.testing.assert_array_equal(fr.read(rows, version=v), refs[v])
        np.testing.assert_array_equal(fr.read(rows, version=max(0, v - 2)),
                                      refs[max(0, v - 2)])
    assert fr.stats().reads_served == 2 * len(wl.batches)


def test_reads_interleave_with_pending_writes():
    """Reads submitted *before* batches are served at their pinned version
    at the next micro-batch point, and staleness accounts the gap."""
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=4)
    cfg = _cfg(model, wl, x)
    rows = np.arange(0, wl.base.n, 11)
    refs = _serial_reference("offload", cfg, wl, rows)

    fr = ServingFrontend(create_engine("offload", cfg))
    tickets = []
    for b in wl.batches:
        tickets.append(fr.submit_read(rows))  # pinned at current version
        fr.apply_batch(b)  # serves the read before applying (staleness 0)
    late = fr.submit_read(rows, version=1)  # served 3 batches late
    fr.drain()
    for v, t in enumerate(tickets):
        assert t.version == v and t.staleness == 0
        np.testing.assert_array_equal(t.value(), refs[v])
    np.testing.assert_array_equal(late.value(), refs[1])
    assert late.staleness == len(wl.batches) - 1
    assert fr.stats().staleness_batches == len(wl.batches) - 1


def test_older_pin_queued_behind_fresh_reads_is_served():
    """A read pinned a version back, queued behind fresh reads of other
    rows, is served first (lower pin) out of the middle of the queue."""
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=2)
    cfg = _cfg(model, wl, x)
    rows = np.arange(0, wl.base.n, 7)
    refs = _serial_reference("device", cfg, wl, rows)

    fr = ServingFrontend(create_engine("device", cfg))
    fr.apply_batch(wl.batches[0])
    fresh = [fr.submit_read(rows[i::3]) for i in range(3)]
    pinned = fr.submit_read(rows, version=0)
    fr.drain()
    np.testing.assert_array_equal(pinned.value(), refs[0])
    for i, t in enumerate(fresh):
        np.testing.assert_array_equal(t.value(), refs[1][i::3])


# ---------------------------------------------------------------------- #
# admission control / backpressure
# ---------------------------------------------------------------------- #
def test_backpressure_evicts_oldest_version_with_typed_error():
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=3)
    fr = ServingFrontend(create_engine("offload", _cfg(model, wl, x)),
                         max_pending_reads=2)
    for b in wl.batches:
        fr.apply_batch(b)
    rows = np.arange(8)
    t0 = fr.submit_read(rows, version=0)
    t1 = fr.submit_read(rows, version=1)
    t2 = fr.submit_read(rows, version=2)  # queue full → t0 (oldest pin) out
    assert t0.done and isinstance(t0.error, ReadRejectedError)
    with pytest.raises(ReadRejectedError):
        t0.value()
    assert not t1.done and not t2.done
    assert fr.drain() == 2
    assert t1.value() is not None and t2.value() is not None
    ss = fr.stats()
    assert ss.reads_rejected == 1 and ss.reads_served == 2


def test_stale_pin_rejected_below_undo_floor():
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=4)
    fr = ServingFrontend(create_engine("offload", _cfg(model, wl, x)),
                         max_versions=2)
    for b in wl.batches:
        fr.apply_batch(b)
    assert fr.version == 4 and fr.min_version == 2
    with pytest.raises(StaleVersionError):
        fr.submit_read(np.arange(4), version=1)
    assert fr.stats().reads_rejected == 1
    # the floor itself is still servable
    assert fr.read(np.arange(4), version=2).shape == (4, 8)


def test_refresh_clears_undo_history():
    """An orchestrator refresh recomputes state from scratch — older
    versions stop being reconstructible and the floor jumps."""
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=4)
    cfg = _cfg(model, wl, x, refresh_every=2)
    rows = np.arange(0, wl.base.n, 9)
    refs = _serial_reference("device", cfg, wl, rows)

    fr = ServingFrontend(create_engine("device", cfg), max_versions=8)
    fr.apply_batch(wl.batches[0])
    fr.apply_batch(wl.batches[1])  # refresh fires after this batch
    assert fr.min_version == fr.version == 2
    with pytest.raises(StaleVersionError):
        fr.submit_read(rows, version=1)
    fr.apply_batch(wl.batches[2])
    np.testing.assert_array_equal(fr.read(rows, version=2), refs[2])
    np.testing.assert_array_equal(fr.read(rows, version=3), refs[3])


def test_future_pin_waits_for_version():
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=3)
    cfg = _cfg(model, wl, x)
    rows = np.arange(0, wl.base.n, 13)
    refs = _serial_reference("offload", cfg, wl, rows)
    fr = ServingFrontend(create_engine("offload", cfg))
    t = fr.submit_read(rows, version=2)
    fr.apply_batch(wl.batches[0])
    assert not t.done  # version 1 < pin
    fr.apply_batch(wl.batches[1])
    fr.apply_batch(wl.batches[2])  # serves at version 2 before batch 3
    assert t.done and t.staleness == 0
    np.testing.assert_array_equal(t.value(), refs[2])


# ---------------------------------------------------------------------- #
# unified factory (API redesign satellite)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_factory_bitwise_parity_with_direct_construction(backend):
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=4)
    params = model.init_layers(jax.random.PRNGKey(0), [8, 8])
    direct = {
        "device": lambda: RTECEngine(model, params, wl.base, jnp.asarray(x)),
        "offload": lambda: OffloadedRTECEngine(model, params, wl.base, x),
        "sharded": lambda: ShardedRTECEngine(model, params, wl.base, x),
        "sharded_offload": lambda: ShardedOffloadRTECEngine(
            model, params, wl.base, x),
        "chunked": lambda: ChunkedRTECEngine(model, params, wl.base, x),
    }[backend]()
    fact = create_engine(backend, EngineConfig(model=model, graph=wl.base,
                                               x=x, params=params))
    assert type(fact) is type(direct)
    for b in wl.batches:
        direct.apply_batch(b)
        fact.apply_batch(b)
    np.testing.assert_array_equal(np.asarray(fact.embeddings),
                                  np.asarray(direct.embeddings))


def test_engine_config_param_init_and_validation():
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=1)
    cfg = EngineConfig(model=model, graph=wl.base, x=x, dims=[8, 8, 8],
                       seed=7)
    eng = create_engine("device", cfg)
    assert eng.L == 2
    with pytest.raises(ValueError, match="unknown backend"):
        create_engine("hbm", cfg)
    with pytest.raises(ValueError, match="params or dims"):
        create_engine("device", EngineConfig(model=model, graph=wl.base, x=x))


def test_serving_frontend_helper_on_every_facade():
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=1)
    for backend in BACKENDS:
        eng = create_engine(backend, _cfg(model, wl, x))
        fr = eng.serving_frontend(max_versions=3)
        assert isinstance(fr, ServingFrontend) and fr.max_versions == 3


# ---------------------------------------------------------------------- #
# chunked substrate wired into the public API (orphan-code satellite)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["gcn", "gat"])
def test_chunked_backend_matches_full_recompute(name):
    """`backend="chunked"` executes real streams correctly with multiple
    chunks per layer (chunk_size < affected-set size forces chunking and
    the inter-chunk staging-reuse path)."""
    model = make_model(name)
    x, wl = _mk_stream(num_batches=8, seed=3)
    params = model.init_layers(jax.random.PRNGKey(0), [8, 8])
    cfg = EngineConfig(model=model, graph=wl.base, x=x, params=params,
                       chunk_size=8)
    eng = create_engine("chunked", cfg)
    for b in wl.batches:
        eng.apply_batch(b)
    g_cur, x_cur = wl.base, np.array(x)
    for b in wl.batches:
        g_cur = g_cur.apply_updates(b.ins_src, b.ins_dst, b.del_src,
                                    b.del_dst, b.ins_weights, b.ins_etypes)
        if b.feat_vertices is not None:
            x_cur[b.feat_vertices] = b.feat_values
    ref = np.asarray(full_forward(model, params, jnp.asarray(x_cur),
                                  g_cur)[-1].h)
    assert float(np.abs(eng.embeddings - ref).max()) < TOL
    assert eng.chunk_stats.chunks > len(wl.batches)  # chunking really ran


# ---------------------------------------------------------------------- #
# StreamStats as the single result type (results satellite)
# ---------------------------------------------------------------------- #
def test_stream_stats_as_dict_defaults_and_read_fields():
    d = StreamStats([], 0.0, 0.0).as_dict()
    # read-side fields default to zero so pre-serving baselines keep passing
    for k in ("reads_served", "reads_rejected", "staleness_batches"):
        assert d[k] == 0
    model = make_model("gcn")
    x, wl = _mk_stream(num_batches=2)
    fr = ServingFrontend(create_engine("offload", _cfg(model, wl, x)))
    ss = fr.run_stream(wl.batches)
    # rounds that served nothing leave no per-round record
    assert fr.read_rounds == []
    assert isinstance(ss, StreamStats) and len(ss.batches) == 2
    d = ss.as_dict()
    assert d["n_batches"] == 2 and d["wall_s"] == ss.wall_s
    assert set(d) >= {"staged_bytes", "prefetch_hits", "reads_served",
                      "staleness_batches"}
    fr.submit_read([0, 1, 1])
    fr.submit_read([1, 2], version=fr.version - 1)
    assert fr.drain() == 2
    (r,) = fr.read_rounds
    assert (r.reads, r.groups, r.union_rows) == (2, 2, 4)
