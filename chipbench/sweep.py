#!/usr/bin/env python3
"""Several runs of one cell in one process, for finding a cell's load and
its correctness limits (the benchmark's own runs never call this).

    python3 chipbench/sweep.py --workload sage3-arxiv.live28 --seconds 20 \
        --seeds 11 12 13 --rates 20 30 --control 1

Each seed × rate pair is one ``run_cell`` with the mix's update rate
replaced by the given one (``--rates`` omitted: the mix's own).  With
``--control 1`` each run is a control run: the reference computed one
precision step down, three bfloat16 passes, stands in for the window's
output and goes through the same comparison, so its ``correct`` has to be
false; the program's own readings of the same run come under
``"program"``.  One JSON line per run on standard output, then a summary
line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import NoAccelerator, enable_compile_cache, run_cell

    enable_compile_cache(ROOT)

    rows = []
    t_start = T_PROCESS
    for rate in args.rates or [None]:
        for seed in args.seeds:
            mix = None if rate is None else {"update_rate_per_s": rate}
            try:
                out = run_cell(args.workload, seed, args.seconds, False,
                               root=ROOT, t_process=t_start,
                               mix_overrides=mix, control=bool(args.control))
            except NoAccelerator as e:
                print(f"chipbench: {e}", file=sys.stderr)
                return 2
            out["seed"], out["rate"] = seed, rate
            print(json.dumps(out), flush=True)
            rows.append({"seed": seed, "rate": rate, "correct": out["correct"],
                         "checks": {k: v["value"] for k, v in out["checks"].items()},
                         "program": out.get("program"),
                         "metrics": {k: v["value"] for k, v in out["metrics"].items()}})
            t_start = time.perf_counter()
    print(json.dumps({"summary": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
