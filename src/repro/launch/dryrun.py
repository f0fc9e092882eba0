import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell and extract the roofline terms (DESIGN.md §8, EXPERIMENTS.md
§Dry-run).

MUST be the process entry point — the XLA_FLAGS line above runs before any
other import (jax locks the device count at first init).  Results are
persisted per cell under experiments/dryrun/<cell>.json so the sweep is
resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, SHAPES, get_arch
from repro.dist.ctx import activation_sharding
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
    shardings_for_cell,
)
from repro.train.optimizer import OptConfig

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# v5e per-chip constants of the dry-run cost model only: the compile-only
# sweep here has no device to ask, and nothing on the engine's chip path
# (chip_smoke.py, repro.core, repro.serve) reads them
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9
ICI_BW = 50e9  # per link


def input_specs(arch: str, shape_name: str = "train_4k"):
    """ShapeDtypeStruct stand-ins for every model input of the given cell —
    weak-type-correct, shardable, no device allocation.  Training shapes
    return the {tokens, labels, frames?, patches?} batch; decode shapes also
    return the abstract cache pytree."""
    from repro.launch.steps import batch_struct, serve_cache_struct

    cfg = production_cfg(arch)
    shape = SHAPES[shape_name]
    out = dict(batch_struct(cfg, shape))
    if shape.kind == "decode":
        out["cache"] = serve_cache_struct(
            cfg, shape.global_batch, shape.seq_len + (cfg.num_patches or 0)
        )
        out["token"] = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return out


def cell_skipped(arch: str, shape_name: str) -> str:
    cfg = get_arch(arch)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return "pure full-attention arch — long_500k needs sub-quadratic attention (DESIGN.md §5)"
    return ""


def production_cfg(arch: str):
    return dataclasses.replace(get_arch(arch), param_dtype="bfloat16")


def run_cell(arch: str, shape_name: str, multi_pod: bool, mode: str = "opt") -> dict:
    # mode: 'baseline' = XLA propagation only; 'opt' = explicit activation
    # sharding constraints (ashard) — the main §Perf lever.
    import contextlib

    cfg = production_cfg(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    sh = shardings_for_cell(cfg, shape, mesh)
    ctx = (
        activation_sharding(mesh, sh["shcfg"])
        if mode == "opt"
        else contextlib.nullcontext()
    )

    t0 = time.time()
    with ctx:
        if shape.kind == "train":
            step = make_train_step(cfg, OptConfig())
            jitted = jax.jit(
                step,
                in_shardings=(sh["params_sharding"], sh["opt_sharding"], sh["batch_sharding"]),
            )
            lowered = jitted.lower(sh["params_struct"], sh["opt_struct"], sh["batch_struct"])
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, sh["s_max"])
            bstruct = dict(sh["batch_struct"])
            bstruct.pop("labels")
            bsh = dict(sh["batch_sharding"])
            bsh.pop("labels")
            jitted = jax.jit(step, in_shardings=(sh["params_sharding"], bsh))
            lowered = jitted.lower(sh["params_struct"], bstruct)
        else:  # decode
            step = make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(sh["params_sharding"], sh["cache_sharding"], sh["token_sharding"]),
            )
            lowered = jitted.lower(sh["params_struct"], sh["cache_struct"], sh["token_struct"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # train/prefill: attention matrices stream through VMEM on TPU (flash
    # kernel) — exclude them from the HBM term (they exist only in the CPU
    # lowering).  decode keeps the raw number (it uses the XLA path on TPU).
    hint = shape.seq_len if shape.kind in ("train", "prefill") else None
    stats = analyze_hlo(hlo, default_trip_count=cfg.num_layers,
                        total_devices=n_chips, attn_seq_hint=hint)

    compute_s = stats.flops / PEAK_FLOPS
    hbm_eff = stats.hbm_bytes_flash_adjusted if hint else stats.hbm_bytes
    memory_s = hbm_eff / HBM_BW
    collective_s = stats.collective_bytes / ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", collective_s)],
        key=lambda kv: kv[1],
    )[0]

    n = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_active * tokens
    hlo_flops_total = stats.flops * n_chips

    result = {
        "arch": arch,
        "shape": shape_name,
        "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_est_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 3),
        },
        "xla_cost_analysis": {
            "flops_per_device_unscaled": cost.get("flops", 0.0),
            "bytes_per_device_unscaled": cost.get("bytes accessed", 0.0),
        },
        "hlo_per_device": {
            "flops": stats.flops,
            "hbm_bytes_raw": stats.hbm_bytes,
            "hbm_bytes_flash_adjusted": stats.hbm_bytes_flash_adjusted,
            "attn_matrix_bytes_excluded": stats.attn_matrix_bytes,
            "collective_wire_bytes": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "per_collective_bytes": stats.per_collective_bytes,
        },
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "bound_s": max(compute_s, memory_s, collective_s),
        },
        "model_flops": {
            "params": n,
            "active_params": n_active,
            "model_flops": model_flops,
            "hlo_flops_total": hlo_flops_total,
            "useful_fraction": model_flops / hlo_flops_total if hlo_flops_total else 0.0,
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--mode", choices=["baseline", "opt"], default="baseline")
    args = ap.parse_args()

    out_dir = OUT_DIR / args.mode
    out_dir.mkdir(parents=True, exist_ok=True)
    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                for mp in (False, True):
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells.append((args.arch, args.shape, args.multi_pod))

    for arch, shape_name, mp in cells:
        tag = f"{arch}__{shape_name}__{'pod2' if mp else 'pod1'}"
        out_path = out_dir / f"{tag}.json"
        if out_path.exists() and not args.force:
            print(f"[skip cached] {tag}")
            continue
        skip = cell_skipped(arch, shape_name)
        if skip:
            out_path.write_text(json.dumps({"arch": arch, "shape": shape_name,
                                            "mesh": "2x16x16" if mp else "16x16",
                                            "skipped": skip}, indent=2))
            print(f"[skip] {tag}: {skip}")
            continue
        print(f"[run ] {tag} ...", flush=True)
        try:
            res = run_cell(arch, shape_name, mp, mode=args.mode)
            out_path.write_text(json.dumps(res, indent=2))
            r = res["roofline"]
            print(
                f"[done] {tag}: lower={res['lower_s']}s compile={res['compile_s']}s "
                f"mem={res['memory_analysis']['peak_est_gb']}GB "
                f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
                f"coll={r['collective_s']:.2e}s dominant={r['dominant']}",
                flush=True,
            )
        except Exception as e:  # noqa
            out_path.with_suffix(".err").write_text(traceback.format_exc())
            print(f"[FAIL] {tag}: {e}")


if __name__ == "__main__":
    main()
