"""Shared benchmark helpers: graph/stream setup, method registry, timing,
CSV emission (`name,us_per_call,derived`)."""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    RTECUER,
    MTECPeriod,
    RTECEngine,
    RTECFull,
    RTECSample,
    make_model,
)
from repro.graph import make_graph, make_stream
from repro.graph.generators import random_features

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line, flush=True)


def setup(
    kind: str = "powerlaw",
    n: int = 2000,
    avg_degree: float = 8.0,
    d: int = 16,
    num_batches: int = 5,
    batch_edges: int = 20,
    delete_frac: float = 0.3,
    seed: int = 0,
):
    g = make_graph(kind, n, avg_degree=avg_degree, seed=seed, weighted=True)
    x, _ = random_features(n, d, seed=seed)
    wl = make_stream(g, num_batches=num_batches, batch_edges=batch_edges,
                     delete_frac=delete_frac, seed=seed + 1)
    return g, x, wl


def make_engine(method: str, model, params, base, x):
    x = jnp.asarray(x)
    if method == "inc":
        return RTECEngine(model, params, base, x)
    if method == "full":
        return RTECFull(model, params, base, x)
    if method == "uer":
        return RTECUER(model, params, base, x)
    if method.startswith("ns"):
        return RTECSample(model, params, base, x, fanout=int(method[2:]))
    if method == "period":
        return MTECPeriod(model, params, base, x, period=5)
    raise ValueError(method)


def run_stream(engine, wl) -> Tuple[float, Dict[str, float]]:
    """Apply all batches; returns (mean wall s/batch, aggregate counters).

    Timing is honest: each batch is synced (``jax.block_until_ready``) at
    the timed boundary so async dispatch can't leak a batch's execution into
    its successor's wall time."""
    agg = {"inc_edges": 0, "full_edges": 0, "vertices": 0,
           "plan_s": 0.0, "exec_s": 0.0, "graph_s": 0.0}
    times = []
    for b in wl.batches:
        t0 = time.perf_counter()
        st = engine.apply_batch(b)
        # sync device-side where the engine exposes its state arrays:
        # ShardedRTECEngine's .embeddings is a full D2H gather + reshape,
        # which would charge an O(N·d) host copy to every timed batch
        sync = (engine._sync_arrays() if hasattr(engine, "_sync_arrays")
                else engine.embeddings)
        jax.block_until_ready(sync)
        times.append(time.perf_counter() - t0)
        agg["inc_edges"] += st.inc_edges
        agg["full_edges"] += st.full_edges
        agg["vertices"] += st.out_vertices
        agg["plan_s"] += st.plan_time_s
        agg["exec_s"] += st.exec_time_s
        agg["graph_s"] += st.graph_time_s
    # min over post-warmup batches: pow-2 capacity buckets retrace on growth,
    # and a 3-batch mean would charge that compile time to the engine
    t = np.min(times[1:]) if len(times) > 1 else times[0]
    return float(t), agg


def run_stream_pipelined(engine, wl) -> float:
    """Plan/execute-overlapped stream application (RTECEngine.apply_stream).

    Returns honest wall seconds per batch over the steady-state tail: the
    first batch is applied separately as warmup (it pays the fused-step
    compile for the stream's shape buckets), then the rest run pipelined."""
    warm, rest = wl.batches[0], wl.batches[1:]
    engine.apply_batch(warm)
    if not rest:
        return 0.0
    ss = engine.apply_stream(rest)
    return ss.wall_s / len(rest)


def gnn_params(model, dims, seed=0):
    return model.init_layers(jax.random.PRNGKey(seed), dims)


def emit_stream_stats(prefix: str, ss, expect_prefetch: int = None,
                      expect_reads: int = None,
                      expect_staleness: int = None) -> None:
    """Emit a StreamStats through its normalized ``as_dict()`` view (the
    single result type, ISSUE 6) as the standard `<prefix>_*` rows:

    * ``<prefix>_stream_wall`` — wall us, ``plan_<v>us`` derived;
    * ``<prefix>_prefetch_hits`` / ``<prefix>_staged_bytes`` — the overlap
      counters (only when ``expect_prefetch`` is given: structural
      expectation for the CI exact gate);
    * ``<prefix>_reads_served`` / ``<prefix>_staleness_batches`` — the
      serving front-end's deterministic read counters (only when
      ``expect_reads`` is given; CI exact gate).
    """
    d = ss.as_dict()
    emit(f"{prefix}_stream_wall", d["wall_s"] * 1e6,
         f"plan_{d['plan_s'] * 1e6:.0f}us")
    if expect_prefetch is not None:
        emit(f"{prefix}_prefetch_hits", float(d["prefetch_hits"]),
             f"expect_{expect_prefetch}")
        emit(f"{prefix}_staged_bytes", float(d["staged_bytes"]),
             f"sync_wait_{d['sync_wait_s'] * 1e6:.0f}us_compute_"
             f"{d['compute_s'] * 1e6:.0f}us")
    if expect_reads is not None:
        emit(f"{prefix}_reads_served", float(d["reads_served"]),
             f"expect_{expect_reads}")
        emit(f"{prefix}_staleness_batches", float(d["staleness_batches"]),
             f"expect_{expect_staleness}")
