"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod = 16×16 v5e ("data", "model");
multi-pod = 2 pods × 16×16 ("pod", "data", "model") — the "pod" axis maps
to the cross-pod DCN/ICI links.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False, pipeline_stages: int = 0):
    """Single pod 16×16 ("data", "model"); multi-pod 2×16×16 ("pod", ...).

    ``pipeline_stages > 1`` carves a leading "stage" axis out of the data
    axis (16 must stay divisible) for `repro.dist.pipeline.pipeline_apply`.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pipeline_stages > 1:
        data_idx = len(shape) - 2
        if shape[data_idx] % pipeline_stages:
            raise ValueError(
                f"pipeline_stages={pipeline_stages} must divide data axis {shape[data_idx]}"
            )
        shape = (*shape[:data_idx], pipeline_stages,
                 shape[data_idx] // pipeline_stages, shape[-1])
        axes = (*axes[:data_idx], "stage", "data", "model")
    n = int(np.prod(shape))
    # the steps constrain activations with with_sharding_constraint, which
    # needs Auto axes (make_mesh defaults to Explicit)
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes, auto)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — run under "
            f'XLA_FLAGS="--xla_force_host_platform_device_count={n}" (dryrun.py sets this)'
        )
    return jax.make_mesh(shape, axes, auto, devices=devices[:n])
