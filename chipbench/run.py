#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload gcn3-arxiv.live14 --seed 7 \
        --seconds 45 --trace 0

The run loads the cell's configuration, builds the graph (cached in the
checkout after the first run), makes its inputs from ``--seed``, warms up,
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON line last on standard output.  With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics instead of the end-to-end ones.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench.harness import NoAccelerator, enable_compile_cache, run_cell

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {platform!r}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       root=ROOT, t_process=T_PROCESS)
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
