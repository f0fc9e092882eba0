"""Block-aligned CSR segment-sum as one-hot MXU matmuls (Pallas TPU).

The TPU-native realization of the paper's scatter-add aggregation hot spot
(DESIGN.md §3, §7).  TPUs have no efficient random scatter; instead, edges
are pre-sorted by destination and padded so that each destination *row tile*
(TV rows) owns an integer number of *edge blocks* (BE edges).  Within a
block the segment-sum becomes

    out_tile[TV, BD] += onehot[TV, BE] @ messages[BE, BD]

an MXU matmul with `onehot[r, e] = (dst_local[e] == r)` — systolic-array
work instead of serial scatters.

Data-dependent output indexing uses `PrefetchScalarGridSpec`: the host
precomputes ``block_rows[i]`` = row-tile index of edge block i (sorted ⇒
non-decreasing), which drives the output BlockSpec.  The grid is ordered
(feature_tiles, edge_blocks) so revisits of an output tile are *consecutive*
— the Pallas accumulation contract — with `pl.when(first-visit)` zeroing.

v5e sizing: BE=512 edges × BD=128 lanes of f32 messages = 256 KiB input
block; TV=8 sublanes × 128 lanes out = 4 KiB; onehot materialized at
[8, 512] = 16 KiB.  Three buffers double-buffered ≈ 0.6 MiB of the 128 MiB
VMEM — leaves room for the wider-D variants the engine uses (BD up to 512).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# default tile sizes (see header); overridable for tests/sweeps
TV = 8  # destination rows per tile (sublane dim)
BE = 512  # edges per block
BD = 128  # feature lanes per block


def prepare_block_csr(
    dst: np.ndarray, num_rows: int, tv: int = TV, be: int = BE
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side block alignment (the 'block-aligned CSR' layout).

    Given dst ids sorted ascending (pad entries = -1 allowed at the end),
    emits (perm, dst_local, block_rows, e_pad):
      perm       [E_pad] gather indices into the edge array (-1 → padding)
      dst_local  [E_pad] destination row *within its tile* (-1 → padding)
      block_rows [E_pad/be] row-tile index per edge block (non-decreasing)
    """
    dst = np.asarray(dst, np.int64)
    valid = dst >= 0
    dstv = dst[valid]
    idxv = np.nonzero(valid)[0]
    assert np.all(np.diff(dstv) >= 0), "dst must be sorted ascending"
    if not dstv.size:  # empty input
        pad = np.full(be, -1, np.int32)
        return pad, pad.copy(), np.zeros(1, np.int32), be
    tiles = dstv // tv
    # sorted dst → each tile's records are one contiguous run; each run is
    # padded to a whole number of edge blocks
    tile_ids, starts, counts = np.unique(tiles, return_index=True,
                                         return_counts=True)
    padded = counts + (-counts) % be
    offsets = np.cumsum(padded) - padded
    e_pad = int(padded.sum())
    pos = np.arange(dstv.size) + np.repeat(offsets - starts, counts)
    perm = np.full(e_pad, -1, np.int32)
    dloc = np.full(e_pad, -1, np.int32)
    perm[pos] = idxv
    dloc[pos] = dstv - tiles * tv
    block_rows = np.repeat(tile_ids, padded // be).astype(np.int32)
    return perm, dloc, block_rows, e_pad


def _kernel(block_rows_ref, dloc_ref, msg_ref, out_ref):
    j, i = pl.program_id(0), pl.program_id(1)
    first = jnp.logical_or(i == 0, block_rows_ref[i] != block_rows_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    dloc = dloc_ref[...].reshape(-1)  # [BE]
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], dloc.shape[0]), 0)
    onehot = (rows == dloc[None, :]).astype(jnp.float32)
    msg = msg_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.dot(onehot, msg, preferred_element_type=jnp.float32).astype(
        out_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("num_rows", "tv", "be", "bd", "interpret"))
def segment_spmm(
    messages: jax.Array,  # [E_pad, D] already permuted to block layout
    dst_local: jax.Array,  # [E_pad] int32 (-1 padding)
    block_rows: jax.Array,  # [NB] int32
    num_rows: int,
    tv: int = TV,
    be: int = BE,
    bd: int = BD,
    interpret: bool = False,
) -> jax.Array:
    """Segment-sum of block-aligned messages. Returns [num_rows_padded, D]
    where num_rows_padded = ceil(num_rows/tv)*tv; caller slices [:num_rows]."""
    e_pad, d = messages.shape
    assert e_pad % be == 0, (e_pad, be)
    assert d % bd == 0, (d, bd)
    nb = e_pad // be
    nd = d // bd
    rows_pad = ((num_rows + tv - 1) // tv) * tv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nd, nb),
        in_specs=[
            pl.BlockSpec((be, 1), lambda j, i, br: (i, 0)),  # dst_local
            pl.BlockSpec((be, bd), lambda j, i, br: (i, j)),  # messages
        ],
        out_specs=pl.BlockSpec((tv, bd), lambda j, i, br: (br[i], j)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_pad, d), messages.dtype),
        interpret=interpret,
        name="segment_spmm",
    )(block_rows, dst_local[:, None], messages)
    return out
