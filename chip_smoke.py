#!/usr/bin/env python3
"""Chip smoke: the served streaming-GNN path once on a TPU, at ogbn-arxiv scale.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip path on a four-chip host

Workload (all seeded, nothing downloaded): a 169,343-vertex power-law graph
(``make_graph("powerlaw", avg_degree=14)``, ~2.37 M directed edges, the
symmetrised size of ogbn-arxiv), random width-128 features, and the 3-layer
hidden-256 40-class GCN of OGB's arxiv example with random weights.  The
stream is 8 batches of 64 edge updates (30 % deletes) plus feature updates
on 16 vertices per batch.  Between batches a ``ServingFrontend`` answers 4
reads of 256 random rows, one of them pinned to the previous version.

One chip runs the ``"device"`` backend twice over the same stream: (a) on
the XLA scatter path and (b) with the Pallas ``delta_agg`` kernel.  With
``--chips 4`` only the row-sharded backends run: ``"sharded"`` (4 shards,
``ppermute`` halo) and ``"sharded_offload"`` (4 shards).  Every phase
compares the final embeddings and the served reads with ``full_forward``
on that version's snapshot, and the script exits non-zero, without the
``ok`` line, if the device is not a TPU, a phase raises or a comparison
fails.  Times printed here are one-off smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Engine and reference both run under "highest" matmul precision: a TPU
# float32 dot otherwise takes bf16 passes (~3 significant digits), which
# would swamp the comparison below.  With it, what separates the engine from
# the reference is float32 summation order.  A sum of k terms carries at
# most k·eps of rounding relative to the sum of their magnitudes; the
# largest in-degree here is about 3,000 (printed as d_max), so one order is
# off by at most ~3.6e-4 and two independent orders by ~7e-4.  TOL = 1e-3,
# taken relative to max(1, max|ref|), covers that bound; a wrong or missed
# row moves an output by O(0.1) and fails it by two orders of magnitude.
MATMUL_PRECISION = "highest"
TOL = 1e-3


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    n: int = 169_343
    avg_degree: float = 14.0
    dims: Sequence[int] = (128, 256, 256, 40)  # dims[0] is the feature width
    batches: int = 8
    batch_edges: int = 64
    delete_frac: float = 0.3
    feat_rows: int = 16
    reads: int = 4  # per service point; the last one is pinned a version back
    read_rows: int = 256
    seed: int = 0


ARXIV = SmokeConfig()


@dataclasses.dataclass
class Workload:
    cfg: SmokeConfig
    model: object
    params: list
    stream: object  # repro.graph.streaming.StreamWorkload
    x: np.ndarray
    refs: Dict[int, np.ndarray]  # version -> final-layer embeddings


@dataclasses.dataclass
class PhaseResult:
    name: str
    max_abs: float
    max_rel: float
    ok: bool
    compiles_after_warmup: int
    reads_served: int
    state_bytes: int
    bytes_in_use: Optional[List[Optional[int]]] = None


def build_workload(cfg: SmokeConfig, log: Callable[[str], None] = print) -> Workload:
    """Graph, features, weights, stream and the full-recompute references
    for the two versions the phases compare (the last, and the one before)."""
    import jax

    from repro.core.full import full_forward
    from repro.core.models import make_model
    from repro.graph.generators import make_graph, random_features
    from repro.graph.streaming import make_stream

    t0 = time.perf_counter()
    g = make_graph("powerlaw", cfg.n, avg_degree=cfg.avg_degree, seed=cfg.seed)
    x, _ = random_features(cfg.n, cfg.dims[0], seed=cfg.seed)
    # make_stream picks int(n · feature_frac) vertices per batch
    stream = make_stream(
        g, num_batches=cfg.batches, batch_edges=cfg.batch_edges,
        delete_frac=cfg.delete_frac, feature_dim=cfg.dims[0],
        feature_frac=(cfg.feat_rows + 0.5) / cfg.n, seed=cfg.seed)
    model = make_model("gcn")
    params = model.init_layers(jax.random.PRNGKey(cfg.seed), list(cfg.dims))
    log(f"workload: V={cfg.n} E_base={stream.base.num_edges} "
        f"dims={list(cfg.dims)} batches={cfg.batches}x{cfg.batch_edges} "
        f"setup_s={time.perf_counter() - t0:.3f}")

    # the reference replays the stream on the graph and feature table alone
    graphs, feats = [stream.base], [x]
    for b in stream.batches:
        graphs.append(graphs[-1].apply_updates(
            b.ins_src, b.ins_dst, b.del_src, b.del_dst, b.ins_weights,
            b.ins_etypes))
        xv = feats[-1].copy()
        if b.feat_vertices is not None:
            xv[b.feat_vertices] = b.feat_values
        feats.append(xv)
    refs = {}
    t0 = time.perf_counter()
    for v in (cfg.batches - 1, cfg.batches):
        states = full_forward(model, params, jax.numpy.asarray(feats[v]), graphs[v])
        refs[v] = np.asarray(states[-1].h)
        del states
    d_max = int(max(gr.in_degree().max() for gr in graphs))
    log(f"reference: versions {sorted(refs)} d_max={d_max} "
        f"E_final={graphs[-1].num_edges} ref_s={time.perf_counter() - t0:.3f}")
    return Workload(cfg, model, params, stream, x, refs)


def _bytes_in_use(devices) -> List[Optional[int]]:
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if stats is None else int(stats.get("bytes_in_use", 0)))
    return out


def run_phase(name: str, backend: str, wl: Workload, comms=None,
              num_shards: Optional[int] = None,
              log: Callable[[str], None] = print) -> PhaseResult:
    """Drive one backend through create_engine → StreamOrchestrator →
    ServingFrontend over the workload's stream, then compare its final
    embeddings and served reads with the references."""
    import jax

    from repro.obs import TALLY
    from repro.serve import EngineConfig, ServingFrontend, create_engine

    cfg = wl.cfg
    t0 = time.perf_counter()
    eng = create_engine(backend, EngineConfig(
        model=wl.model, graph=wl.stream.base, x=wl.x, params=wl.params,
        comms=comms, num_shards=num_shards))
    fe = ServingFrontend(eng)
    log(f"[{name}] engine up: state_bytes={eng.state_bytes()} "
        f"init_s={time.perf_counter() - t0:.3f}")
    rng = np.random.default_rng(cfg.seed + 1)
    tickets = []

    def submit() -> None:
        for r in range(cfg.reads):
            pin = max(fe.version - 1, 0) if r == cfg.reads - 1 else None
            rows = rng.choice(cfg.n, size=cfg.read_rows, replace=False)
            tickets.append(fe.submit_read(rows, version=pin))

    compiles_after = 0
    for i, batch in enumerate(wl.stream.batches):
        submit()
        c0 = TALLY.compiles
        bs = fe.apply_batch(batch)
        new = TALLY.compiles - c0
        if i > 0:
            compiles_after += new
        log(f"[{name}] batch {i}: inc_edges={bs.inc_edges} "
            f"out_rows={bs.out_vertices} compiles={new} "
            f"plan_s={bs.plan_time_s:.6f} step_s={bs.exec_time_s:.6f} "
            "(one-off smoke timing)")
    submit()
    fe.drain()
    final = np.asarray(eng.embeddings)
    bytes_in_use = _bytes_in_use(jax.devices())
    state_bytes = eng.state_bytes()
    del fe, eng

    checks = [(final, wl.refs[cfg.batches])]
    for t in tickets[-cfg.reads:]:
        checks.append((t.value(), wl.refs[t.version][t.rows]))
    scale = max(float(np.abs(ref).max()) for _, ref in checks)
    max_abs = max(float(np.abs(got - ref).max()) for got, ref in checks)
    finite = all(np.isfinite(got).all() for got, _ in checks)
    shapes = all(got.shape == ref.shape for got, ref in checks)
    n_reads = cfg.reads * (cfg.batches + 1)
    served = sum(t.result is not None for t in tickets)
    ok = (finite and shapes and served == n_reads
          and max_abs <= TOL * max(1.0, scale))
    res = PhaseResult(name, max_abs, max_abs / max(scale, 1e-30), ok,
                      compiles_after, served, state_bytes, bytes_in_use)
    log(f"[{name}] max_abs_err={res.max_abs!r} max_rel_err={res.max_rel!r} "
        f"tol={TOL}*max(1,{scale!r}) reads_served={served}/{n_reads} "
        f"pinned_version={tickets[-1].version} "
        f"compiles_after_warmup={compiles_after} ok={ok}")
    return res


def one_chip_phases(wl: Workload, log=print) -> List[PhaseResult]:
    from repro.dist.sharding import CommsConfig

    return [
        run_phase("a:device/xla-scatter", "device", wl, log=log),
        run_phase("b:device/pallas-delta_agg", "device", wl,
                  comms=CommsConfig(use_pallas_delta=True), log=log),
    ]


def four_chip_phases(wl: Workload, shards: int = 4,
                     log=print) -> List[PhaseResult]:
    return [
        run_phase(f"sharded/{shards}", "sharded", wl, num_shards=shards,
                  log=log),
        run_phase(f"sharded_offload/{shards}", "sharded_offload", wl,
                  num_shards=shards, log=log),
    ]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded backends over 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    kind = devices[0].device_kind
    print(f"device: platform={platform} device_kind={kind!r} "
          f"count={len(devices)}")
    print(f"compile cache: {enable_compile_cache(ROOT)}")
    from repro.obs import TALLY

    try:
        with jax.default_matmul_precision(MATMUL_PRECISION):
            wl = build_workload(ARXIV)
            if args.chips == 4:
                results = four_chip_phases(wl)
            else:
                results = one_chip_phases(wl)
    except Exception:
        traceback.print_exc()
        return 1
    print(f"xla executables built={TALLY.compiles} "
          f"persistent cache hits={TALLY.cache_hits}")
    failed = [r.name for r in results if not r.ok]
    # nothing on the path may fall back off the chip: no Pallas interpreter,
    # no kernels/ops.py (which picks the jnp oracle off the TPU)
    from repro.core.incremental import _pallas_interpret

    if _pallas_interpret():
        failed.append("Pallas would run in interpret mode")
    if "repro.kernels.ops" in sys.modules:
        failed.append("repro.kernels.ops was imported")
    for r in results:
        if r.bytes_in_use is not None:
            print(f"[{r.name}] bytes_in_use per device: {r.bytes_in_use}")
    if args.chips == 4:
        for r in results:
            if not all(b for b in r.bytes_in_use):
                failed.append(f"{r.name} (a device reports no bytes in use)")
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": platform, "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
