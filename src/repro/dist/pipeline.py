"""Microbatched pipeline parallelism over a mesh "stage" axis.

`pipeline_apply` runs a layer-stacked block function as a GPipe-style
pipeline inside one `shard_map`: each device row along the stage axis owns
one slice of the stacked params, microbatches stream through, and
`lax.ppermute` moves activations stage -> stage+1 each tick.  The schedule
is the classic (num_micro + num_stages - 1)-tick fill/drain loop; numerics
are bit-comparable to `sequential_reference` because every microbatch sees
the identical op sequence, just on a different device per step.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import rotation_perm


def sequential_reference(block: Callable[[Any, jax.Array], jax.Array],
                         params, x: jax.Array) -> jax.Array:
    """Single-device reference: apply the S stacked stages in order.

    `params` is a pytree whose leaves all carry a leading stage dim S;
    stage s runs `block(params[s], x)`.
    """
    num_stages = jax.tree.leaves(params)[0].shape[0]
    for s in range(num_stages):
        stage_params = jax.tree.map(lambda a: a[s], params)  # noqa: B023
        x = block(stage_params, x)
    return x


def pipeline_apply(
    block: Callable[[Any, jax.Array], jax.Array],
    params,
    x: jax.Array,
    mesh,
    stage_axis: str = "stage",
    num_micro: int = 4,
) -> jax.Array:
    """Pipeline-parallel `sequential_reference` over `mesh`'s stage axis.

    The leading dim of every param leaf is split across `stage_axis`
    (stage s's params live on device row s); the batch dim of `x` is split
    into `num_micro` microbatches that stream through the stages.  Any
    other mesh axes (e.g. "model") see replicated data — compose tensor
    parallelism inside `block` via `ashard` if wanted.
    """
    num_stages = int(mesh.shape[stage_axis])
    batch = x.shape[0]
    if batch % num_micro != 0:
        raise ValueError(f"batch {batch} not divisible by num_micro={num_micro}")
    stage_dim = jax.tree.leaves(params)[0].shape[0]
    if stage_dim != num_stages:
        raise ValueError(
            f"params leading dim {stage_dim} != mesh '{stage_axis}' size {num_stages}"
        )
    micro = batch // num_micro
    xs = x.reshape(num_micro, micro, *x.shape[1:])

    param_specs = jax.tree.map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), params
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(local_params, xs_all):
        idx = lax.axis_index(stage_axis)
        stage_params = jax.tree.map(lambda a: a[0], local_params)
        fwd = rotation_perm(num_stages)  # stage -> stage+1 each tick

        def tick(t, carry):
            state, out_buf = carry
            # stage 0 injects microbatch t (clamped; ticks past the fill
            # phase recompute a stale microbatch whose output is never kept)
            mb = xs_all[jnp.minimum(t, num_micro - 1)]
            inp = jnp.where(idx == 0, mb, state)
            y = block(stage_params, inp)
            # the last stage finished microbatch m = t - (num_stages - 1)
            m = t - (num_stages - 1)
            keep = jnp.logical_and(idx == num_stages - 1, m >= 0)
            slot = jnp.clip(m, 0, num_micro - 1)
            out_buf = out_buf.at[slot].set(jnp.where(keep, y, out_buf[slot]))
            state = lax.ppermute(y, stage_axis, fwd)
            return state, out_buf

        ticks = num_micro + num_stages - 1
        _, out_buf = lax.fori_loop(
            0, ticks, tick, (jnp.zeros_like(xs_all[0]), jnp.zeros_like(xs_all))
        )
        # only the last stage holds real outputs; psum broadcasts them
        mask = (idx == num_stages - 1).astype(out_buf.dtype)
        return lax.psum(out_buf * mask, stage_axis)

    out = run(params, xs)
    return out.reshape(batch, *x.shape[1:])
