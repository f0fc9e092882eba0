"""In-place signed delta aggregation (Pallas TPU, aliased state update).

The incremental hot path of Alg. 1 line 5: ``a[dst[e]] += sign·msg[e]`` over
the affected-edge records, *in place* on the cached aggregation state.  Uses
the same block-aligned one-hot-MXU schedule as :mod:`segment_spmm`, plus
``input_output_aliases`` so the state tensor is updated without a second
HBM copy — the TPU equivalent of NeutronRT's in-place GPU scatter.

Only state tiles named in ``block_rows`` are touched; all other rows pass
through untouched via the aliased buffer (this is what makes the update
O(affected) in HBM traffic instead of O(V)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes shared by the fused-step delta scatter: the host planner
# (affected.pack_plan) emits the block-CSR schedule with these, and the
# device step (incremental.fused_stream_step) calls the kernel with the
# same — they must agree or the BlockSpecs read the wrong tiles.
DELTA_TV = 8  # state rows per tile
DELTA_BE = 128  # records per edge block (streams are small; 512 overpads)
DELTA_BD = 128  # feature lanes per block (Mosaic f32 tiling needs lane dim ≥128)


def _kernel(block_rows_ref, dloc_ref, msg_ref, state_ref, out_ref):
    i = pl.program_id(1)
    first = jnp.logical_or(i == 0, block_rows_ref[i] != block_rows_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        out_ref[...] = state_ref[...]

    dloc = dloc_ref[...]  # [1, be]
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], dloc.shape[1]), 0)
    onehot = (rows == dloc).astype(jnp.float32)
    msg = msg_ref[...].astype(jnp.float32)
    # the one-hot product is a scatter-add: it must keep every bit of the
    # float32 messages, which a default-precision (bf16-pass) TPU dot drops
    out_ref[...] += jnp.dot(onehot, msg, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tv", "be", "bd", "interpret"))
def delta_agg(
    messages: jax.Array,  # [E_pad, D] signed, block-aligned layout
    dst_local: jax.Array,  # [E_pad] int32 (-1 padding)
    block_rows: jax.Array,  # [NB] int32 (non-decreasing)
    state: jax.Array,  # [rows_pad, D] — updated in place (donated)
    tv: int = 8,
    be: int = 512,
    bd: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e_pad, d = messages.shape
    nb = e_pad // be
    nd = d // bd
    assert state.shape[0] % tv == 0 and state.shape[1] == d

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nd, nb),
        in_specs=[
            # lane-major destinations: a [E, 1] column would be tiled to
            # 128 lanes in HBM, 128x the bytes of the indices themselves
            pl.BlockSpec((None, 1, be), lambda j, i, br: (i, 0, 0)),
            pl.BlockSpec((be, bd), lambda j, i, br: (i, j)),
            pl.BlockSpec((tv, bd), lambda j, i, br: (br[i], j)),  # state (read)
        ],
        out_specs=pl.BlockSpec((tv, bd), lambda j, i, br: (br[i], j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={3: 0},  # alias state → out (after scalar operand)
        interpret=interpret,
        name="delta_agg",
    )(block_rows, dst_local.reshape(nb, 1, be), messages, state)
