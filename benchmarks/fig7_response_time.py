"""Paper Fig. 7: per-batch response time + throughput (edge updates/s)
across six GNN models × methods, in-memory processing.

Also hosts the serving-frontend cells (ISSUE 6): the smoke job's
deterministic read-counter cell (`smoke_frontend`, CI-gated exactly) and
the full sweep's read-pressure-vs-throughput curve (`run_serving`,
telemetry)."""
from __future__ import annotations

from benchmarks.common import (
    emit,
    emit_stream_stats,
    gnn_params,
    make_engine,
    run_stream,
    run_stream_pipelined,
    setup,
)
from repro.core import make_model

MODELS = ["gcn", "sage", "gin", "monet", "agnn", "gat"]
METHODS = ["full", "ns10", "ns5", "uer", "inc"]


def smoke():
    """Tiny cells for the CI benchmark-smoke job — well under a minute on
    one CPU (EXPERIMENTS.md §Perf).  Emits the blocking perf-gate metric
    matrix (benchmarks/check_regression.py): gcn speedup (unconstrained
    path), gat speedup (§IV-C constrained path), and the offload engine's
    deterministic transfer-row volume."""
    # 6 batches → the steady-state min is over 5 post-warmup samples, which
    # keeps the gated ratios stable against one-off scheduler/GC spikes
    _, x, wl = setup("powerlaw", n=300, avg_degree=4.0, num_batches=6, batch_edges=8)
    for mname in ("gcn", "gat"):
        model = make_model(mname)
        params = gnn_params(model, [16, 16])
        times = {}
        for method in ("full", "inc"):
            eng = make_engine(method, model, params, wl.base, x)
            t, _ = run_stream(eng, wl)
            times[method] = t
            emit(f"fig7/smoke/{mname}/{method}", t * 1e6, "")
        emit(f"fig7/smoke/{mname}/inc_speedup_vs_full", times["inc"] * 1e6,
             f"{times['full'] / times['inc']:.2f}x")
        if mname == "gcn":
            # plan/execute overlap (non-gating).  apply_stream reports one
            # wall time for the whole overlapped run, so unlike run_stream's
            # per-batch min a single scheduler/GC spike or mid-stream retrace
            # is charged to the entire measurement: take the best of a few
            # fresh-engine repeats instead.
            t_pipe = min(
                run_stream_pipelined(
                    make_engine("inc", model, params, wl.base, x), wl)
                for _ in range(3)
            )
            emit("fig7/smoke/gcn/inc_pipelined", t_pipe * 1e6,
                 f"{times['full'] / t_pipe:.2f}x")
    # offload transfer volume: deterministic row counts, tight gate bound.
    # Runs through the unified apply_stream (ISSUE 4: the offload engine
    # returns the same StreamStats as every other engine) — staging and
    # write-back volume is identical to the per-batch path.
    from repro.serve.offload import OffloadedRTECEngine

    model = make_model("gcn")
    params = gnn_params(model, [16, 16])
    # min over 3 fresh-engine repeats, same rationale as inc_pipelined
    # above: a single apply_stream wall charges every per-shape-bucket jit
    # compile of incremental_layer (~2.4s, >95% of the old 2403ms cell) to
    # a 6-batch stream; the repeats share the in-process jit cache, so the
    # min measures the steady-state stream the serving path actually runs
    off = ss = None
    for _ in range(3):
        eng = OffloadedRTECEngine(model, params, wl.base, x)
        s = eng.apply_stream(wl.batches)
        if ss is None or s.wall_s < ss.wall_s:
            off, ss = eng, s  # keep wall and plan_s from the same run;
            # the gated counters are deterministic across repeats
    # overlap metric set (ISSUE 5) — deterministic counters, CI-gated:
    # prefetch_hits is structural (every batch after the first plans while
    # the previous executes), staged_bytes is a plan-determined payload
    # volume; sync_wait vs compute is telemetry only (timing noise).
    # Rows render through StreamStats.as_dict (the single result type).
    emit_stream_stats("fig7/smoke/gcn/offload", ss,
                      expect_prefetch=len(wl.batches) - 1)
    emit("fig7/smoke/gcn/offload_transfer_rows",
         float(off.transfers.total_rows), f"{off.transfers.total_rows}rows")
    smoke_frontend(model, params, wl, x)
    smoke_cache()
    smoke_fusion()


def smoke_fusion():
    """Batch-window fusion cell (ISSUE 9): a high-rate small-batch stream
    of region-disjoint updates on a ring lattice — the workload fusion is
    built for (each batch's plan is tiny and independent, so dispatch
    overhead dominates).  Runs the offload engine fused (window=4) vs
    serial, fails the step outright on any embedding divergence (fusion
    must be bitwise invisible), and emits the exact fusion counters
    (expectations shared with the gate via
    ``check_regression.FUSION_EXPECTED``): 12 fusable batches under a
    4-deep lookahead fuse into exactly 3 windows, so the stream executes
    in 3 device dispatches instead of 12 — the dispatch count drops by
    exactly ``fused_batches - fusion_windows``."""
    import numpy as np

    from benchmarks.check_regression import FUSION_EXPECTED
    from repro.core import make_model
    from repro.graph.csr import CSRGraph
    from repro.graph.generators import random_features
    from repro.graph.streaming import UpdateBatch
    from repro.serve import EngineConfig, FusionConfig, create_engine

    n, num, d = 600, 12, 8
    # ring lattice (in-edges from i+1, i+2): updates confined to regions
    # 45 rows apart have provably disjoint L=2 footprints, so every
    # window's independence check passes — the counters are structural
    idx = np.arange(n, dtype=np.int64)
    src = np.concatenate([(idx + 1) % n, (idx + 2) % n])
    dst = np.concatenate([idx, idx])
    g = CSRGraph.from_edges(n, src, dst)
    rng = np.random.default_rng(0)
    batches = []
    for i in range(num):
        base = (i * 45) % n
        batches.append(UpdateBatch(
            ins_src=np.array([(base + 1) % n], np.int64),
            ins_dst=np.array([(base + 5) % n], np.int64),
            del_src=np.array([], np.int64),
            del_dst=np.array([], np.int64),
            feat_vertices=np.array([(base + 7) % n], np.int64),
            feat_values=rng.standard_normal((1, d)).astype(np.float32)))
    x, _ = random_features(n, d, seed=0)
    model = make_model("gcn")
    params = gnn_params(model, [d, d])
    runs = {}
    for fused in (False, True):
        eng = create_engine("offload", EngineConfig(
            model=model, graph=g, x=x, params=params,
            fusion=FusionConfig(window=4) if fused else None))
        ss = eng.apply_stream(batches)
        runs[fused] = (np.asarray(eng.embeddings), ss.as_dict())
    emb_s, d_s = runs[False]
    emb_f, d_f = runs[True]
    exp = FUSION_EXPECTED
    # dispatch count: every batch outside a window is one dispatch, every
    # window is one dispatch — the identity the test suite pins per-cell
    dispatches = num - (d_f["fused_batches"] - d_f["fusion_windows"])
    emit("fig7/smoke/gcn/fusion_windows", float(d_f["fusion_windows"]),
         f"expect_{exp['windows']}")
    emit("fig7/smoke/gcn/fusion_fused_batches", float(d_f["fused_batches"]),
         f"expect_{exp['fused_batches']}")
    emit("fig7/smoke/gcn/fusion_dispatches", float(dispatches),
         f"expect_{exp['dispatches']}")
    failures = []
    if d_f["fusion_fallbacks"] != 0:
        failures.append(
            f"fusion_fallbacks={d_f['fusion_fallbacks']} on an all-fusable "
            "stream (expected 0)")
    if d_s["fusion_windows"] != 0 or d_s["fused_batches"] != 0:
        failures.append("serial run reported nonzero fusion counters")
    if not np.array_equal(emb_s, emb_f):
        diff = float(np.abs(emb_s - emb_f).max())
        failures.append(
            f"fused-vs-serial max|diff|={diff:g} (expected bitwise 0)")
    if failures:
        raise SystemExit("fusion smoke gate FAILED: " + "; ".join(failures))


def smoke_cache():
    """Device hot-row cache cell (ISSUE 8): the offload engine over the
    deterministic hub_burst stream, cached vs uncached.  Emits the gated
    ratio row (uncached/cached staged bytes — the acceptance's ≥30%
    reduction is a 1.43x floor) and the exact hit/miss/eviction counters
    (expectations shared with the gate via
    ``check_regression.CACHE_EXPECTED``), and fails the step outright on
    any cached-vs-uncached embedding divergence — the cache must be
    bitwise invisible to the math."""
    import numpy as np

    from benchmarks.check_regression import CACHE_EXPECTED
    from repro.core import make_model
    from repro.graph import make_adversarial_stream
    from repro.graph.generators import random_features
    from repro.serve import CacheConfig, EngineConfig, create_engine

    wl = make_adversarial_stream("hub_burst", num_batches=6)
    x, _ = random_features(wl.base.n, 8, seed=0)
    model = make_model("gcn")
    params = gnn_params(model, [8, 8])
    runs = {}
    for cached in (False, True):
        eng = create_engine("offload", EngineConfig(
            model=model, graph=wl.base, x=x, params=params,
            cache=CacheConfig(capacity_rows=256) if cached else None))
        ss = eng.apply_stream(wl.batches)
        runs[cached] = (np.asarray(eng.embeddings), ss.as_dict())
    emb_u, d_u = runs[False]
    emb_c, d_c = runs[True]
    exp = CACHE_EXPECTED["smoke"]
    ratio = d_u["staged_bytes"] / max(d_c["staged_bytes"], 1)
    emit("fig7/smoke/gcn/cache_staged_bytes", float(d_c["staged_bytes"]),
         f"{ratio:.2f}x")
    emit("fig7/smoke/gcn/cache_hit_rows", float(d_c["cache_hit_rows"]),
         f"expect_{exp['hit_rows']}")
    emit("fig7/smoke/gcn/cache_miss_rows", float(d_c["cache_miss_rows"]),
         f"expect_{exp['miss_rows']}")
    emit("fig7/smoke/gcn/cache_evictions", float(d_c["cache_evictions"]),
         f"expect_{exp['evictions']}")
    if not np.array_equal(emb_u, emb_c):
        diff = float(np.abs(emb_u - emb_c).max())
        raise SystemExit(
            f"cache smoke gate FAILED: cached-vs-uncached max|diff|={diff:g} "
            "(expected bitwise 0)")


def smoke_frontend(model, params, wl, x):
    """Serving front-end smoke cell (ISSUE 6): reads interleaved with the
    existing 6-batch stream on the offload engine, deterministic schedule —
    before batch i one read pinned at the current version i plus, once
    version ≥ 2, one pinned at i-2.  Over 6 batches that is 10 served reads
    with cumulative staleness 8 (4 × 2 batches), both CI-gated exactly."""
    import numpy as np

    from repro.serve import ServingFrontend, create_engine, EngineConfig

    eng = create_engine("offload", EngineConfig(
        model=model, graph=wl.base, x=x, params=params))
    fr = ServingFrontend(eng, max_pending_reads=16, max_versions=4)
    rows = np.arange(0, wl.base.n, 17)
    for b in wl.batches:
        fr.submit_read(rows)  # pinned at the current version
        if fr.version >= 2:
            fr.submit_read(rows, version=fr.version - 2)
        fr.apply_batch(b)
    fr.drain()
    n_fresh = len(wl.batches)
    n_stale = len(wl.batches) - 2
    emit_stream_stats("fig7/smoke/gcn/frontend", fr.stats(),
                      expect_reads=n_fresh + n_stale,
                      expect_staleness=2 * n_stale)


def smoke_sharded(num_shards: int):
    """Sharded-engine smoke cell (the CI multi-device job's artifact):
    single-device pipelined engine vs :class:`ShardedRTECEngine` on the same
    stream, plus the per-batch frontier (halo) row count the psum exchange
    is bounded to, and the sharded-vs-single max |Δ| as an equivalence
    telemetry row."""
    import numpy as np

    from repro.core import ShardedRTECEngine

    _, x, wl = setup("powerlaw", n=300, avg_degree=4.0, num_batches=6, batch_edges=8)
    model = make_model("gcn")
    params = gnn_params(model, [16, 16])
    single = make_engine("inc", model, params, wl.base, x)
    t_single, _ = run_stream(single, wl)
    emit("fig7/sharded/gcn/single", t_single * 1e6, "")
    sharded = ShardedRTECEngine(model, params, wl.base, x, num_shards=num_shards)
    t_sharded, _ = run_stream(sharded, wl)
    emit(f"fig7/sharded/gcn/sharded{num_shards}", t_sharded * 1e6,
         f"{t_single / t_sharded:.2f}x")
    halo_per_batch = sharded.halo_rows_total / len(wl.batches)
    emit("fig7/sharded/gcn/halo_rows_per_batch", halo_per_batch,
         f"S={num_shards}")
    diff = float(np.abs(np.asarray(single.embeddings) - sharded.embeddings).max())
    emit("fig7/sharded/gcn/max_abs_diff_vs_single", diff, "")
    # ---- sharded-offload hybrid cell (ISSUE 4) ----
    from repro.serve.offload import ShardedOffloadRTECEngine

    hybrid = ShardedOffloadRTECEngine(model, params, wl.base, x,
                                      num_shards=num_shards)
    t_hybrid, _ = run_stream(hybrid, wl)
    emit(f"fig7/sharded/gcn/hybrid{num_shards}", t_hybrid * 1e6,
         f"{t_single / t_hybrid:.2f}x")
    diff_h = float(np.abs(np.asarray(single.embeddings) - hybrid.embeddings).max())
    emit("fig7/sharded/gcn/hybrid_max_abs_diff_vs_single", diff_h, "")
    # per-shard H2D+D2H row volume: deterministic (no timing noise), gated
    # by check_regression's sharded suite — growth means the per-shard
    # compact staging or remap tables regressed toward O(V) transfers
    rows_per_shard = int(hybrid.per_shard_rows.max())
    emit("fig7/sharded/gcn/hybrid_transfer_rows_per_shard",
         float(rows_per_shard), f"S={num_shards}")
    emit("fig7/sharded/gcn/hybrid_peak_device_bytes",
         float(hybrid.peak_device_bytes),
         f"state_{hybrid.state_bytes()}B")
    # hybrid overlap cell (ISSUE 5): a fresh engine runs the overlapped
    # stream path so the staging pipeline's deterministic counters can be
    # gated (check_regression --suite sharded) without disturbing the
    # per-batch transfer accounting gated above
    hybrid_pipe = ShardedOffloadRTECEngine(model, params, wl.base, x,
                                           num_shards=num_shards)
    ssh = hybrid_pipe.apply_stream(wl.batches)
    emit_stream_stats("fig7/sharded/gcn/hybrid", ssh,
                      expect_prefetch=len(wl.batches) - 1)
    diff_p = float(np.abs(np.asarray(single.embeddings)
                          - hybrid_pipe.embeddings).max())
    emit("fig7/sharded/gcn/hybrid_stream_max_abs_diff_vs_single", diff_p, "")
    # the cell gates correctness + halo/transfer volume, not wall time (on
    # CPU CI the forced "devices" oversubscribe the cores): fail the CI step
    # outright on divergence (the gcn path is exact for both engines) or on
    # halo traffic past the frontier-only bound (~12 rows/batch measured; 64
    # leaves headroom for workload drift while still catching a
    # broadcast-everything regression against the 300-vertex graph)
    failures = []
    if diff != 0.0:
        failures.append(f"sharded-vs-single max|diff|={diff:g} (expected 0)")
    if diff_h != 0.0:
        failures.append(f"hybrid-vs-single max|diff|={diff_h:g} (expected 0)")
    if diff_p != 0.0:
        failures.append(
            f"hybrid-stream-vs-single max|diff|={diff_p:g} (expected 0)")
    if halo_per_batch > 64:
        failures.append(f"halo_rows_per_batch={halo_per_batch:.1f} exceeds 64")
    failures += _sharded_cache_cell(num_shards)
    failures += _sharded_comms_cell(num_shards, model, params, wl, x)
    if failures:
        raise SystemExit("sharded smoke gate FAILED: " + "; ".join(failures))


def _sharded_comms_cell(num_shards, model, params, wl, x):
    """Per-consumer halo exchange (ISSUE 10): the ppermute send-recv
    schedules vs the legacy global-frontier psum on the same deterministic
    stream.  Emits the gated ``comms_halo_rows_sent`` (exact: unique
    (owner, consumer, row) deliveries are a pure function of the plans)
    and the psum broadcast volume as its pinned ceiling; fails the CI step
    outright on any embedding divergence (the two modes are bitwise-equal
    by construction) or if the per-consumer volume is not strictly below
    the broadcast ceiling.  Returns failure strings for the caller's
    SystemExit."""
    import numpy as np

    from benchmarks.check_regression import COMMS_EXPECTED
    from repro.dist.sharding import CommsConfig
    from repro.serve import EngineConfig, create_engine

    runs = {}
    for mode in ("psum", "ppermute"):
        eng = create_engine("sharded", EngineConfig(
            model=model, graph=wl.base, x=x, params=params,
            num_shards=num_shards, comms=CommsConfig(halo=mode)))
        ss = eng.apply_stream(wl.batches)
        runs[mode] = (np.asarray(eng.embeddings), ss)
    emb_p, ss_p = runs["psum"]
    emb_q, ss_q = runs["ppermute"]
    exp = COMMS_EXPECTED["sharded"]
    emit("fig7/sharded/gcn/comms_halo_rows_sent",
         float(ss_q.comms_halo_rows_sent),
         f"expect_{exp['halo_rows_sent']}")
    emit("fig7/sharded/gcn/comms_halo_bytes",
         float(ss_q.comms_halo_bytes), f"S={num_shards}")
    emit("fig7/sharded/gcn/comms_psum_ceiling_rows",
         float(ss_p.comms_halo_rows_sent),
         f"expect_{exp['psum_ceiling_rows']}")
    failures = []
    if not np.array_equal(emb_p, emb_q):
        diff = float(np.abs(emb_p - emb_q).max())
        failures.append(
            f"ppermute-vs-psum max|diff|={diff:g} (expected 0)")
    if not 0 < ss_q.comms_halo_rows_sent < ss_p.comms_halo_rows_sent:
        failures.append(
            f"comms_halo_rows_sent={ss_q.comms_halo_rows_sent} not "
            f"strictly below the psum broadcast ceiling "
            f"{ss_p.comms_halo_rows_sent}")
    return failures


def _sharded_cache_cell(num_shards: int):
    """Hot-row cache on the sharded offload hybrid (ISSUE 8): hub_burst
    cached vs uncached, same contract as ``smoke_cache`` — ratio-gated
    staged bytes plus exact residency counters.  Returns failure strings
    (the caller folds them into the sharded gate's SystemExit).  The
    pinned ``CACHE_EXPECTED['sharded']`` counts assume the CI job's 8-way
    mesh: per-shard halo rows make residency S-dependent."""
    import numpy as np

    from benchmarks.check_regression import CACHE_EXPECTED
    from repro.graph import make_adversarial_stream
    from repro.graph.generators import random_features
    from repro.serve import CacheConfig, EngineConfig, create_engine

    wl = make_adversarial_stream("hub_burst", num_batches=6)
    x, _ = random_features(wl.base.n, 8, seed=0)
    model = make_model("gcn")
    params = gnn_params(model, [8, 8])
    runs = {}
    for cached in (False, True):
        eng = create_engine("sharded_offload", EngineConfig(
            model=model, graph=wl.base, x=x, params=params,
            num_shards=num_shards,
            cache=CacheConfig(capacity_rows=256) if cached else None))
        ss = eng.apply_stream(wl.batches)
        runs[cached] = (np.asarray(eng.embeddings), ss.as_dict())
    emb_u, d_u = runs[False]
    emb_c, d_c = runs[True]
    exp = CACHE_EXPECTED["sharded"]
    ratio = d_u["staged_bytes"] / max(d_c["staged_bytes"], 1)
    emit("fig7/sharded/gcn/hybrid_cache_staged_bytes",
         float(d_c["staged_bytes"]), f"{ratio:.2f}x")
    emit("fig7/sharded/gcn/hybrid_cache_hit_rows",
         float(d_c["cache_hit_rows"]), f"expect_{exp['hit_rows']}")
    emit("fig7/sharded/gcn/hybrid_cache_miss_rows",
         float(d_c["cache_miss_rows"]), f"expect_{exp['miss_rows']}")
    emit("fig7/sharded/gcn/hybrid_cache_evictions",
         float(d_c["cache_evictions"]), f"expect_{exp['evictions']}")
    if not np.array_equal(emb_u, emb_c):
        diff = float(np.abs(emb_u - emb_c).max())
        return [f"hybrid cached-vs-uncached max|diff|={diff:g} (expected 0)"]
    return []


def run(quick: bool = True):
    n = 2000 if quick else 8000
    g, x, wl = setup("powerlaw", n=n, avg_degree=8.0, num_batches=4, batch_edges=16)
    upd_per_batch = wl.batches[0].num_updates
    for mname in MODELS:
        model = make_model(mname)
        params = gnn_params(model, [16, 16, 16])
        times = {}
        for method in METHODS:
            eng = make_engine(method, model, params, wl.base, x)
            t, agg = run_stream(eng, wl)
            times[method] = t
            thpt = upd_per_batch / t
            emit(f"fig7/{mname}/{method}", t * 1e6, f"{thpt:.0f}_upd_per_s")
        for method in ("full", "uer", "ns10"):
            emit(
                f"fig7/{mname}/inc_speedup_vs_{method}", times["inc"] * 1e6,
                f"{times[method] / times['inc']:.2f}x",
            )
    run_serving(x, wl)


def run_serving(x, wl):
    """Read-pressure-vs-throughput serving cells (full sweep only):
    the gcn offload engine under increasing read pressure — r reads per
    update batch, each pinned one version back — reporting update
    throughput.  Telemetry rows (timing on a shared CI host is noise); the
    deterministic read counters are gated in the *smoke* cell."""
    import numpy as np

    from repro.serve import EngineConfig, ServingFrontend, create_engine

    model = make_model("gcn")
    params = gnn_params(model, [16, 16, 16])
    upd_per_batch = wl.batches[0].num_updates
    rows = np.arange(0, wl.base.n, 7)
    # un-emitted warmup stream: charge the per-shape-bucket jit compiles
    # here, not to the first sweep point (the inc_pipelined precedent —
    # otherwise the r=0 cell eats ~10s of compile and the curve reads
    # backwards)
    warm = create_engine("offload", EngineConfig(
        model=model, graph=wl.base, x=x, params=params))
    warm.apply_stream(wl.batches)
    for r in (0, 1, 4, 16):
        eng = create_engine("offload", EngineConfig(
            model=model, graph=wl.base, x=x, params=params))
        fr = ServingFrontend(eng, max_pending_reads=4 * max(r, 1) + 1)
        for b in wl.batches:
            for _ in range(r):
                fr.submit_read(rows, version=max(0, fr.version - 1))
            fr.apply_batch(b)
        fr.drain()
        ss = fr.stats()
        thpt = upd_per_batch * len(wl.batches) / max(ss.wall_s, 1e-9)
        emit(f"fig7/serving/gcn/reads{r}_throughput", ss.wall_s * 1e6,
             f"{thpt:.0f}_upd_per_s")
