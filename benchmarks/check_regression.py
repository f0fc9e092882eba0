"""Blocking CI perf-regression gate over the bench-smoke artifact.

Usage (what .github/workflows/ci.yml runs after ``benchmarks.run --smoke``):

    python -m benchmarks.check_regression \
        --current BENCH_smoke.json --baseline BENCH_baseline.json

The gate watches a small **metric matrix** (``SPECS``), not a single cell:

* ``fig7/smoke/gcn/inc_speedup_vs_full`` — the headline unconstrained-path
  speedup (the paper's claim is a *speedup*, so losing to full recompute is
  always a regression: absolute floor 1.2x);
* ``fig7/smoke/gat/inc_speedup_vs_full`` — the constrained
  (destination-dependent) path, which exercises the §IV-C full-recompute
  branch the gcn cell never touches;
* ``fig7/smoke/gcn/offload_transfer_rows`` — the offload engine's H2D+D2H
  row volume, a *deterministic* count (no timing noise): growth means the
  compact row sets or remap tables regressed;
* ``fig7/smoke/gcn/frontend_reads_served`` / ``_staleness_batches`` — the
  serving front-end's deterministic read counters from its fixed
  interleaving schedule, gated exactly.
* ``fig7/smoke/gcn/cache_staged_bytes`` + ``cache_hit_rows`` /
  ``cache_miss_rows`` / ``cache_evictions`` — the hot-row cache set
  (ISSUE 8): the staged-bytes row carries the uncached/cached reduction
  ratio on the deterministic hub_burst cell (floor 1.43x, i.e. the
  ≥30% reduction acceptance bound with margin) and the counters are
  exact (``CACHE_EXPECTED``, shared with the emitting cell; the sharded
  suite gates the hybrid's ``hybrid_cache_*`` mirror rows).

Every gated cell now reports through ``StreamStats.as_dict()`` (the single
result type) via ``benchmarks.common.emit_stream_stats``.

Speedup metrics fail when they drop below their absolute ``floor`` or
regress more than ``tolerance`` vs the committed baseline; volume metrics
fail when they *exceed* their ``ceiling`` or grow more than ``tolerance``;
``exact`` metrics (the overlap counters, which are deterministic) must
equal the expectation the emitting cell embeds in their derived column
(``expect_<v>``) and the committed baseline value bit-for-bit.  The
baseline file is committed; refresh it deliberately (rerun
``python -m benchmarks.run --smoke`` and copy the artifact) when a PR
legitimately shifts the perf envelope.

Exit codes are distinct so CI can retry *noise* without masking a metric
that was never emitted (the noise-retry bug, ISSUE 5):

* ``0`` — all gated metrics pass;
* ``1`` — a metric regressed (timing metrics may be runner noise: CI
  gives the whole gate one fresh measurement before failing the build);
* ``2`` — a gated metric is **missing** from the current artifact (or the
  artifact is unreadable).  Never retried: the emitting cell is broken.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Optional, Sequence, Tuple

METRIC = "fig7/smoke/gcn/inc_speedup_vs_full"

EXIT_OK = 0
EXIT_REGRESSION = 1
EXIT_MISSING = 2


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str  # "speedup": derived '<v>x' column, higher is better;
    #            "volume": value column, lower is better;
    #            "exact": deterministic counter — must equal the
    #            'expect_<v>' derived column and the baseline exactly
    floor: Optional[float] = None  # speedup: absolute minimum
    ceiling: Optional[float] = None  # volume: absolute maximum
    tolerance: float = 0.2  # max fractional regression vs baseline


SPECS = (
    MetricSpec(name=METRIC, kind="speedup", floor=1.2, tolerance=0.20),
    MetricSpec(name="fig7/smoke/gat/inc_speedup_vs_full", kind="speedup",
               floor=1.1, tolerance=0.25),
    # deterministic offload metrics: row volume must never grow
    # (tolerance 0 — "unchanged" is the contract; shrinking is a win), and
    # the overlap counters must hit their structural expectations exactly
    MetricSpec(name="fig7/smoke/gcn/offload_transfer_rows", kind="volume",
               ceiling=20000.0, tolerance=0.0),
    MetricSpec(name="fig7/smoke/gcn/offload_prefetch_hits", kind="exact"),
    # measured 145560B on the smoke stream; the ceiling leaves ~35%
    # headroom for planner drift while catching an O(V)-staging regression
    # (full-state staging would be ~10x) — 5% creep tolerance vs baseline
    MetricSpec(name="fig7/smoke/gcn/offload_staged_bytes", kind="volume",
               ceiling=200_000.0, tolerance=0.05),
    # serving front-end read counters (ISSUE 6): the smoke cell's read
    # schedule is deterministic (one fresh + one two-back pinned read per
    # batch once version ≥ 2 → 10 served, cumulative staleness 8), so both
    # counters gate BLOCKING and exactly
    MetricSpec(name="fig7/smoke/gcn/frontend_reads_served", kind="exact"),
    MetricSpec(name="fig7/smoke/gcn/frontend_staleness_batches",
               kind="exact"),
    # device hot-row cache (ISSUE 8): the hub_burst cell runs the offload
    # engine cached vs uncached on the same deterministic stream.  The
    # staged-bytes row is gated as a *ratio* (uncached/cached ≥ 1.43x —
    # the acceptance's ≥30% reduction), and the hit/miss/eviction counters
    # gate exactly (tolerance 0): residency is a pure function of the
    # plans, so any drift is a cache or planner change, never noise.
    MetricSpec(name="fig7/smoke/gcn/cache_staged_bytes", kind="speedup",
               floor=1.43, tolerance=0.10),
    MetricSpec(name="fig7/smoke/gcn/cache_hit_rows", kind="exact"),
    MetricSpec(name="fig7/smoke/gcn/cache_miss_rows", kind="exact"),
    MetricSpec(name="fig7/smoke/gcn/cache_evictions", kind="exact"),
    # batch-window fusion (ISSUE 9): the high-rate small-batch cell's
    # stream is structurally fusable (region-disjoint updates on a ring
    # lattice), so the window/absorbed-batch counters and the resulting
    # dispatch count — n_batches − (fused_batches − fusion_windows) — are
    # pure functions of the plans and gate exactly (tolerance 0).  Any
    # drift means the footprint-disjointness check or the lookahead
    # window regressed; the emitting cell additionally fails the step on
    # any fused-vs-serial embedding divergence (bitwise contract).
    MetricSpec(name="fig7/smoke/gcn/fusion_windows", kind="exact"),
    MetricSpec(name="fig7/smoke/gcn/fusion_fused_batches", kind="exact"),
    MetricSpec(name="fig7/smoke/gcn/fusion_dispatches", kind="exact"),
)

# Gated against BENCH_sharded.json by the multi-device CI job
# (``--suite sharded``): the hybrid's per-shard H2D+D2H row volume is
# deterministic, so growth means the per-shard compact staging or remap
# tables regressed toward O(V) transfers (an O(V)-per-shard regression on
# the 300-vertex smoke graph would exceed 9000 rows).  The overlap
# counters of the hybrid's apply_stream cell are gated the same way as
# the smoke suite's.
SHARDED_SPECS = (
    MetricSpec(name="fig7/sharded/gcn/hybrid_transfer_rows_per_shard",
               kind="volume", ceiling=2500.0, tolerance=0.15),
    MetricSpec(name="fig7/sharded/gcn/hybrid_prefetch_hits", kind="exact"),
    # measured 568320B (S=8, cap-padded per-shard staging buffers)
    MetricSpec(name="fig7/sharded/gcn/hybrid_staged_bytes", kind="volume",
               ceiling=750_000.0, tolerance=0.05),
    # hot-row cache on the hybrid (ISSUE 8): same contract as the smoke
    # suite's cache set — ratio-gated staged bytes, exact residency counts
    MetricSpec(name="fig7/sharded/gcn/hybrid_cache_staged_bytes",
               kind="speedup", floor=1.43, tolerance=0.10),
    MetricSpec(name="fig7/sharded/gcn/hybrid_cache_hit_rows", kind="exact"),
    MetricSpec(name="fig7/sharded/gcn/hybrid_cache_miss_rows", kind="exact"),
    MetricSpec(name="fig7/sharded/gcn/hybrid_cache_evictions", kind="exact"),
    # per-consumer halo exchange (ISSUE 10): rows-sent under ppermute is
    # the number of unique (owner, consumer, row) deliveries — a pure
    # function of the plans, gated exactly (tolerance 0).  The ceiling
    # row pins the global-frontier psum broadcast volume the exchange
    # replaced; the emitting cell (fig7_response_time._sharded_comms_cell)
    # additionally fails the CI step unless rows_sent is strictly below
    # it with bitwise-equal embeddings.
    MetricSpec(name="fig7/sharded/gcn/comms_halo_rows_sent", kind="exact"),
    MetricSpec(name="fig7/sharded/gcn/comms_psum_ceiling_rows",
               kind="exact"),
)

#: ISSUE-8 hot-row-cache expectations on the deterministic hub_burst smoke
#: stream (n=256, 6 batches, CacheConfig(capacity_rows=256)), shared by the
#: emitting cells (benchmarks/fig7_response_time.py) and the exact gates
#: above so bench and gate cannot drift apart.  Residency is a pure
#:  function of the Alg.-4 plans: hit/miss/eviction counts are bit-stable
#: run to run.  The ``sharded`` row is pinned for the CI multi-device
#: job's 8-way mesh (per-shard halo rows make the counts S-dependent).
CACHE_EXPECTED = {
    "smoke": {"hit_rows": 580, "miss_rows": 504, "evictions": 0},
    "sharded": {"hit_rows": 616, "miss_rows": 532, "evictions": 0},
}

#: ISSUE-10 per-consumer halo-exchange expectations on the deterministic
#: sharded smoke stream (powerlaw n=300, 6 batches, the CI multi-device
#: job's 8-way mesh), shared by the emitting cell
#: (fig7_response_time._sharded_comms_cell) and the exact gates above.
#: ``halo_rows_sent`` counts unique (owner, consumer, row) ppermute
#: deliveries over the stream; ``psum_ceiling_rows`` is the legacy
#: global-frontier broadcast volume (halo rows × S) the exchange
#: replaced — both are pure functions of the Alg.-4 plans.
COMMS_EXPECTED = {
    "sharded": {"halo_rows_sent": 157, "psum_ceiling_rows": 584},
}

#: ISSUE-9 batch-window-fusion expectations on the deterministic fusable
#: smoke stream (ring lattice n=600, 12 region-disjoint batches,
#: FusionConfig(window=4)), shared by the emitting cell
#: (fig7_response_time.smoke_fusion) and the exact gates above.  The
#: greedy maximal-prefix fuser packs 12 independent batches under a
#: 4-deep lookahead into 3 full windows, so the stream executes in
#: 12 − (12 − 3) = 3 device dispatches.
FUSION_EXPECTED = {"windows": 3, "fused_batches": 12, "dispatches": 3}

#: per-regime structural expectations for the adaptive policy on the
#: default adversarial streams (benchmarks/adversarial.py imports this
#: table to embed the expect_<v> columns, so the emitting cell and the
#: gate share one source of truth): exact decision counts and the raw
#: edge-work total of the adaptive run.
ADVERSARIAL_EXPECTED = {
    "hub_burst": {"incremental": 4, "chunked": 0, "full": 2,
                  "policy_edges": 3168},
    "delete_heavy": {"incremental": 3, "chunked": 0, "full": 3,
                     "policy_edges": 1608},
    "feature_churn": {"incremental": 3, "chunked": 3, "full": 0,
                      "policy_edges": 4524},
}


def _adversarial_specs(regime: str) -> Tuple[MetricSpec, ...]:
    """The ISSUE-7 policy metric set for one adversarial regime:

    * the three per-mode decision counts, gated **exactly** (BLOCKING) —
      the streams are deterministic, so any drift is a policy or planner
      change, never noise;
    * the raw edge-work ceiling (tolerance 0: deterministic volume);
    * the policy-vs-best-fixed cost ratio in the cost model's edge-work
      units — the adaptive per-batch argmin over mode-independent plans
      is ≤ every fixed mode by construction, so the deterministic ratio
      is ≥ 1.0; the 0.91 floor is the acceptance bound "within 1.1× of
      the best fixed mode";
    * the same ratio in wall time — 2-core-runner noise plus compile
      jitter at n=256 scale, so the floor is generous and the structure
      is carried by the exact counters above.
    """
    exp = ADVERSARIAL_EXPECTED[regime]
    return (
        MetricSpec(name=f"adversarial/{regime}/policy_incremental_batches",
                   kind="exact"),
        MetricSpec(name=f"adversarial/{regime}/policy_chunked_batches",
                   kind="exact"),
        MetricSpec(name=f"adversarial/{regime}/policy_full_batches",
                   kind="exact"),
        MetricSpec(name=f"adversarial/{regime}/policy_edges", kind="volume",
                   ceiling=float(exp["policy_edges"]), tolerance=0.0),
        MetricSpec(name=f"adversarial/{regime}/policy_cost_vs_best_fixed",
                   kind="speedup", floor=0.91, tolerance=0.05),
        MetricSpec(name=f"adversarial/{regime}/policy_wall_vs_best_fixed",
                   kind="speedup", floor=0.30, tolerance=0.60),
    )


SUITES = {"smoke": SPECS, "sharded": SHARDED_SPECS}
SUITES["adversarial"] = tuple(
    spec for regime in ADVERSARIAL_EXPECTED
    for spec in _adversarial_specs(regime))
for _regime in ADVERSARIAL_EXPECTED:
    SUITES[f"adversarial-{_regime}"] = _adversarial_specs(_regime)


def load_row_names(path: str) -> List[str]:
    """All row names of a bench artifact (raises ValueError on any shape
    surprise so callers can map it to the exit-2 path, not a traceback)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except json.JSONDecodeError as e:
        raise ValueError(f"{path}: artifact is not valid JSON: {e}")
    rows = data.get("rows") if isinstance(data, dict) else None
    if not isinstance(rows, list):
        raise ValueError(f"{path}: artifact has no 'rows' list")
    return [str(r).split(",", 2)[0] for r in rows]


def missing_namespace_rows(current: str, baseline: str,
                           specs: Sequence[MetricSpec]) -> List[str]:
    """Baseline rows under a gated cell's namespace that the candidate
    artifact no longer emits.

    A renamed bench cell leaves the stale names in the committed baseline;
    before this check they were silently ignored (the per-spec loop only
    looks up spec names), so the rename could pass the retry path without
    anyone refreshing the baseline.  Any such row is exit-2 material —
    re-measuring cannot conjure a renamed metric."""
    try:
        base_names = load_row_names(baseline)
    except (FileNotFoundError, ValueError):
        return []  # no baseline at all → absolute bounds only, as before
    try:
        cur_names = set(load_row_names(current))
    except (FileNotFoundError, ValueError) as e:
        return [f"candidate artifact unreadable: {e}"]
    roots = tuple({spec.name.rsplit("/", 1)[0] + "/" for spec in specs})
    return [
        f"baseline row {name!r} is in a gated namespace but missing from "
        f"{current} (renamed bench cell? refresh the baseline)"
        for name in base_names
        if name.startswith(roots) and name not in cur_names
    ]


def read_row(path: str, metric: str) -> Tuple[float, str]:
    """Extract one metric row from a smoke artifact as (value, derived)."""
    with open(path) as f:
        data = json.load(f)
    for row in data.get("rows", []):
        name, value, derived = row.split(",", 2)
        if name == metric:
            return float(value), derived
    raise KeyError(f"{path}: metric {metric!r} not found")


def read_metric(path: str, metric: str, kind: str = "speedup") -> float:
    """Extract one metric from a smoke artifact: the '1.53x' derived column
    for speedups, the us_per_call value column for volumes/exact."""
    value, derived = read_row(path, metric)
    if kind == "speedup":
        if not derived.endswith("x"):
            raise ValueError(
                f"{path}: metric {metric!r} has no speedup column: "
                f"{metric},{value},{derived}"
            )
        return float(derived[:-1])
    return value


def read_speedup(path: str, metric: str = METRIC) -> float:
    return read_metric(path, metric, kind="speedup")


def check(current: float, baseline: Optional[float], floor: float,
          tolerance: float, metric: str = METRIC) -> List[str]:
    """Speedup-metric check; returns failure messages (empty → passes)."""
    failures = []
    if current < floor:
        failures.append(
            f"{metric} = {current:.2f}x is below the absolute floor {floor:.2f}x"
        )
    if baseline is not None:
        min_ok = baseline * (1.0 - tolerance)
        if current < min_ok:
            failures.append(
                f"{metric} = {current:.2f}x regressed >{tolerance:.0%} vs "
                f"baseline {baseline:.2f}x (min allowed {min_ok:.2f}x)"
            )
    return failures


def check_volume(current: float, baseline: Optional[float], ceiling: float,
                 tolerance: float, metric: str) -> List[str]:
    """Volume-metric check (lower is better)."""
    failures = []
    if current > ceiling:
        failures.append(
            f"{metric} = {current:.0f} exceeds the absolute ceiling {ceiling:.0f}"
        )
    if baseline is not None:
        max_ok = baseline * (1.0 + tolerance)
        if current > max_ok:
            failures.append(
                f"{metric} = {current:.0f} grew >{tolerance:.0%} vs "
                f"baseline {baseline:.0f} (max allowed {max_ok:.0f})"
            )
    return failures


def check_exact(current: float, derived: str, baseline: Optional[float],
                metric: str) -> List[str]:
    """Exact-counter check: the emitting cell embeds its structural
    expectation in the derived column (``expect_<v>``); the counter must
    match it and the committed baseline bit-for-bit (no tolerance —
    these are deterministic functions of the plan, not timings)."""
    failures = []
    if not derived.startswith("expect_"):
        failures.append(
            f"{metric} derived column {derived!r} carries no expect_<v> "
            "expectation (emitting cell broken)"
        )
    else:
        expect = float(derived[len("expect_"):])
        if current != expect:
            failures.append(
                f"{metric} = {current:.0f} != structural expectation "
                f"{expect:.0f} (overlap pipeline degraded)"
            )
    if baseline is not None and current != baseline:
        failures.append(
            f"{metric} = {current:.0f} != baseline {baseline:.0f} "
            "(deterministic counter changed)"
        )
    return failures


def check_spec(spec: MetricSpec, current: float, baseline: Optional[float],
               derived: str = "") -> List[str]:
    if spec.kind == "speedup":
        return check(current, baseline, spec.floor, spec.tolerance, spec.name)
    if spec.kind == "exact":
        return check_exact(current, derived, baseline, spec.name)
    return check_volume(current, baseline, spec.ceiling, spec.tolerance, spec.name)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--current", default="BENCH_smoke.json")
    ap.add_argument("--baseline", default="BENCH_baseline.json")
    ap.add_argument("--suite", choices=sorted(SUITES), default="smoke",
                    help="metric matrix to gate: 'smoke' for the "
                         "single-device artifact, 'sharded' for the "
                         "multi-device BENCH_sharded.json artifact")
    args = ap.parse_args()

    failures: List[str] = []
    missing: List[str] = []
    for msg in missing_namespace_rows(args.current, args.baseline,
                                      SUITES[args.suite]):
        print(f"MISSING: {msg}", file=sys.stderr)
        missing.append(msg)
    for spec in SUITES[args.suite]:
        try:
            value, derived = read_row(args.current, spec.name)
            if spec.kind == "speedup":
                if not derived.endswith("x"):
                    raise ValueError(
                        f"{args.current}: metric {spec.name!r} has no "
                        f"speedup column: {derived!r}")
                current = float(derived[:-1])
            else:
                current = value
            if spec.kind == "exact" and not derived.startswith("expect_"):
                # the emitting cell no longer embeds its expectation —
                # that is a broken emitter, not a perf regression
                raise ValueError(
                    f"{args.current}: exact metric {spec.name!r} carries "
                    f"no expect_<v> derived column: {derived!r}")
        except (FileNotFoundError, KeyError, ValueError) as e:
            print(f"MISSING: {e}", file=sys.stderr)
            missing.append(spec.name)
            continue
        try:
            baseline = read_metric(args.baseline, spec.name, spec.kind)
        except (FileNotFoundError, KeyError, ValueError):
            print(f"note: no baseline for {spec.name}; absolute bound only")
            baseline = None
        base_str = f"{baseline:.2f}" if baseline is not None else "n/a"
        bound = {"speedup": f"floor={spec.floor:.2f}x" if spec.floor else "",
                 "volume": f"ceiling={spec.ceiling:.0f}" if spec.ceiling else "",
                 "exact": f"exact[{derived}]"}[spec.kind]
        print(f"perf gate: {spec.name} current={current:.2f} "
              f"baseline={base_str} {bound} tolerance={spec.tolerance:.0%}")
        failures += check_spec(spec, current, baseline, derived)

    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    if missing:
        print(f"MISSING METRICS (exit {EXIT_MISSING}, never retried): "
              f"{', '.join(missing)}", file=sys.stderr)
        return EXIT_MISSING
    if not failures:
        print("perf gate passed (all metrics)")
    return EXIT_REGRESSION if failures else EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
