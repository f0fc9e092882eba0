# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig2,table4,...]
    PYTHONPATH=src python -m benchmarks.run --smoke   # <60s; BENCH_smoke.json
    PYTHONPATH=src python -m benchmarks.run --smoke --devices 8
                                            # sharded smoke; BENCH_sharded.json

Each module reproduces one paper artifact (DESIGN.md §8).  `--full` uses the
larger graph sizes; default (quick) finishes on one CPU in minutes.
`--smoke` runs the tiny fig7 cells (including the serving-frontend read
cell, ISSUE 6) and writes `BENCH_smoke.json` — the CI benchmark-smoke job
gates on it (benchmarks/check_regression.py).  All stream cells emit
through `StreamStats.as_dict()` (`benchmarks.common.emit_stream_stats`),
the repo's single result type.
`--adversarial [--regime R]` runs the ISSUE-7 adversarial-stream policy
matrix (3 regimes × {adaptive policy, 3 fixed modes} on the device engine)
and writes `BENCH_adversarial.json` — the CI tests-adversarial matrix job
fans one job per regime and gates the per-regime decision counts exactly
via `benchmarks.check_regression --suite adversarial-<regime>`.
`--devices N` is a CPU rehearsal: it forces `JAX_PLATFORMS=cpu` with N
virtual host devices (XLA flag set **before** jax imports, which is why all
heavy imports live inside the entry points) and, with `--smoke`, runs the
sharded-engine + sharded-offload-hybrid cells instead, writing
`BENCH_sharded.json` — uploaded as an artifact by the CI multi-device job
and gated there via `benchmarks.check_regression --suite sharded`
(deterministic per-shard transfer-row volume).  It never measures a chip;
`chip_smoke.py --chips 4` runs the sharded path on four real chips.
The persistent compilation cache follows `JAX_COMPILATION_CACHE_DIR`, or
else lives in `<checkout>/.jax_cache` (`repro.compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path


def _module_registry():
    from benchmarks import (
        fig10_breakdown,
        fig12_sensitivity,
        fig2_edge_volume,
        fig7_response_time,
        fig8_access_volume,
        roofline,
        table4_accuracy,
        table5_degree,
        table6_memory,
    )

    return {
        "fig2": fig2_edge_volume,
        "table4": table4_accuracy,
        "fig7": fig7_response_time,
        "fig8": fig8_access_volume,
        "fig10": fig10_breakdown,
        "table5": table5_degree,
        "table6": table6_memory,
        "fig12": fig12_sensitivity,
        "roofline": roofline,
    }


def smoke() -> None:
    from benchmarks import fig7_response_time
    from benchmarks.common import ROWS

    t0 = time.time()
    fig7_response_time.smoke()
    wall = time.time() - t0
    out = {"rows": list(ROWS), "wall_s": round(wall, 2)}
    with open("BENCH_smoke.json", "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote BENCH_smoke.json ({wall:.1f}s)")


def adversarial(regime: str = "") -> None:
    from benchmarks import adversarial as cell
    from benchmarks.common import ROWS

    t0 = time.time()
    # always write the artifact, even when a policy gate expectation
    # fails the step — the emitted decision counts and cost ratios ARE
    # the diagnostics, and CI uploads the file `if: always()`
    try:
        cell.run([regime] if regime else None)
    finally:
        wall = time.time() - t0
        out = {"rows": list(ROWS), "wall_s": round(wall, 2)}
        with open("BENCH_adversarial.json", "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote BENCH_adversarial.json ({wall:.1f}s)")


def smoke_sharded(num_shards: int) -> None:
    from benchmarks import fig7_response_time
    from benchmarks.common import ROWS

    t0 = time.time()
    # always write the artifact, even when the correctness/halo gate fails
    # the step — the telemetry rows (max|diff|, halo counts) ARE the
    # diagnostics for that failure, and CI uploads the file `if: always()`
    try:
        fig7_response_time.smoke_sharded(num_shards)
    finally:
        wall = time.time() - t0
        out = {"rows": list(ROWS), "wall_s": round(wall, 2),
               "devices": num_shards}
        with open("BENCH_sharded.json", "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote BENCH_sharded.json ({wall:.1f}s)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fig7 cells, <60s; writes BENCH_smoke.json")
    ap.add_argument("--adversarial", action="store_true",
                    help="adversarial-stream policy matrix (ISSUE 7); "
                         "writes BENCH_adversarial.json")
    ap.add_argument("--regime", type=str, default="",
                    help="with --adversarial: run a single regime "
                         "(hub_burst/delete_heavy/feature_churn) — the CI "
                         "matrix fans one job per regime")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU rehearsal: force JAX_PLATFORMS=cpu with N "
                         "virtual host devices (pre-jax-init), never a chip; "
                         "with --smoke, run the sharded cell and write "
                         "BENCH_sharded.json")
    ap.add_argument("--out", type=str, default="",
                    help="write the emitted rows as a {rows, wall_s} JSON "
                         "artifact (the nightly CI job uploads "
                         "BENCH_nightly.json this way)")
    args = ap.parse_args()
    if args.devices:
        # must land in the env before anything imports jax
        assert "jax" not in sys.modules, "--devices must be set before jax imports"
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}".strip()
        )
        os.environ["JAX_PLATFORMS"] = "cpu"
        print(f"--devices {args.devices}: CPU rehearsal on virtual host "
              "devices, not a chip measurement", file=sys.stderr)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(Path(__file__).resolve().parents[1])
    if args.smoke:
        if args.devices:
            smoke_sharded(args.devices)
        else:
            smoke()
        return
    if args.adversarial:
        adversarial(args.regime)
        return

    from benchmarks.common import ROWS, emit

    modules = _module_registry()
    names = [s for s in args.only.split(",") if s] or list(modules)
    t_run = time.time()
    failed = []
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.time()
        try:
            modules[name].run(quick=not args.full)
            emit(f"{name}/_module_wall_s", (time.time() - t0) * 1e6, "ok")
        except Exception as e:  # noqa
            traceback.print_exc()
            emit(f"{name}/_module_wall_s", (time.time() - t0) * 1e6, f"FAILED:{e}")
            failed.append(name)
    if args.out:
        # write even when a module failed: the partial rows are the
        # diagnostics, and CI uploads the artifact `if: always()`
        with open(args.out, "w") as f:
            json.dump({"rows": list(ROWS),
                       "wall_s": round(time.time() - t_run, 2)}, f, indent=2)
        print(f"wrote {args.out} ({time.time() - t_run:.1f}s)")
    if failed:
        print(f"FAILED modules: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
