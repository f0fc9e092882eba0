"""Device-side reordered incremental RTEC — paper Alg. 1, batched + fused.

Two entry points share one layer body (:func:`_layer_body`):

* :func:`incremental_layer` — the seed per-layer function (one jit dispatch
  per layer, state shipped without scratch rows).  Kept for the offloaded
  engine, ODEC and the dry-run cost model, and as the unfused reference the
  equivalence tests compare the pipelined engine against.
* :func:`fused_stream_step` — the pipelined engine's single L-layer step:
  consumes one :class:`~repro.core.affected.PackedPlan` (three contiguous
  buffers, sliced per field at trace time via the static offset table),
  threads ``(h, a, nct)`` through all layers, and **donates** the state
  arguments so on TPU the cached state updates in place — O(affected) HBM
  traffic instead of an O(V) copy in and out per layer.

The layer body per layer:

  1. recompute local messages for affected edges (old side / new side chosen
     per record) and scatter the *signed* context deltas into the touched
     rows (Alg. 1 lines 1–3);
  2. strip the old neighborhood context from the cached aggregation state of
     the touched rows with ``ms_cbn⁻¹``, add the signed message deltas, and
     re-apply the new context with ``ms_cbn`` (lines 4–6);
  3. full-neighborhood recompute for constrained destination-affected rows
     (paper §IV-C), overwriting their (a, nct);
  4. vertex-wise ``update`` on every row whose output changes (line 7).

All arrays are padded (see :mod:`repro.core.affected`).  State arrays carry
one scratch row at index ``n``; padded indices point there, so padding can
never alias a live vertex regardless of scatter ordering.  The fused step
re-zeroes the scratch row after each layer so the persistent state stays
inert across batches.  Step 1's scatter optionally routes through the Pallas
``delta_agg`` kernel (host-planned block-CSR schedule shipped with the
packed plan; XLA ``segment_sum`` is the fallback).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.affected import (
    HybridLayerLayout,
    PackedLayout,
    ShardedLayout,
    hybrid_layout_slices,
    layout_slices,
    sharded_layout_slices,
)
from repro.core.full import edge_messages, subset_layer
from repro.core.operators import GNNModel, Params
from repro.dist.sharding import rotation_perm


def with_scratch(x: jax.Array) -> jax.Array:
    """Append one zero scratch row (index n) to a [N, ...] array."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)


def _pallas_interpret() -> bool:
    """Pallas runs compiled on a TPU and through its interpreter elsewhere."""
    return jax.default_backend() != "tpu"


def _pallas_delta_scatter(
    raw: jax.Array,  # [Epad, agg] signed, mask-scaled, in block-CSR order
    sched: Tuple[jax.Array, jax.Array, jax.Array],  # (perm, dloc, block_rows)
    r_cap: int,
) -> jax.Array:
    """Step-1 scatter of the aggregation terms via the Pallas ``delta_agg``
    kernel; the schedule was planned host-side in pack_plan, and the
    records already stand in its block order (see :func:`_layer_body`)."""
    from repro.kernels.delta_agg import DELTA_BD, DELTA_BE, DELTA_TV, delta_agg

    _, dloc, brows = sched
    d = raw.shape[1]
    dpad = -(-d // DELTA_BD) * DELTA_BD
    m = raw if dpad == d else jnp.pad(raw, ((0, 0), (0, dpad - d)))
    state = jnp.zeros((r_cap, dpad), m.dtype)  # r_cap is pow2 ≥ 16 → tv-aligned
    out = delta_agg(
        m, dloc, brows, state, tv=DELTA_TV, be=DELTA_BE, bd=DELTA_BD,
        interpret=_pallas_interpret(),
    )
    return out[:, :d]


def _layer_body(
    model: GNNModel,
    p: Params,
    # previous-layer embeddings (old and new views), WITH scratch row [N+1,·]
    h_prev_old: jax.Array,
    h_prev_new: jax.Array,
    deg_old: jax.Array,  # [N+1]
    deg_new: jax.Array,  # [N+1]
    # cached layer state, WITH scratch row [N+1,·]
    a_ext: jax.Array,
    nct_ext: jax.Array,
    h_ext: jax.Array,
    # incremental records
    e_src: jax.Array,
    e_dst: jax.Array,
    e_rowidx: jax.Array,
    e_sign: jax.Array,
    e_use_new: jax.Array,
    e_w: jax.Array,
    e_t: jax.Array,
    e_mask: jax.Array,
    touch_rows: jax.Array,
    touch_mask: jax.Array,
    # constrained full path
    f_rows: jax.Array,
    f_mask: jax.Array,
    f_src: jax.Array,
    f_rowidx: jax.Array,
    f_w: jax.Array,
    f_t: jax.Array,
    f_emask: jax.Array,
    # output rows
    out_rows: jax.Array,
    out_mask: jax.Array,
    f_rows_h: Optional[jax.Array] = None,
    out_rows_h: Optional[jax.Array] = None,
    pallas_delta: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer over scratch-extended state; returns extended arrays."""
    if f_rows_h is None:
        f_rows_h = f_rows
    if out_rows_h is None:
        out_rows_h = out_rows
    r_cap = touch_rows.shape[0]
    f_cap = f_rows.shape[0]

    # each step runs under its jax.named_scope (HLO metadata only), so a
    # trace reduction can name a device op's layer and stage

    # ---------------- step 1: signed delta messages (Alg.1 l.1-3) -------
    with jax.named_scope("messages"):
        if pallas_delta is not None:
            # put the records in the kernel's block-CSR order before
            # computing their messages: permuting the narrow record fields
            # is cheap, while permuting the [E, d] messages would hold a
            # second message-sized buffer in device memory.  Block pads are
            # masked and row-less.
            perm = pallas_delta[0]
            live = perm >= 0
            safe = jnp.where(live, perm, 0)
            e_src, e_dst, e_sign, e_use_new, e_w, e_t = (
                f[safe] for f in (e_src, e_dst, e_sign, e_use_new, e_w, e_t))
            e_rowidx = jnp.where(live, e_rowidx[safe], r_cap)
            e_mask = e_mask[safe] & live
        # one gather per endpoint from the stacked [old | new] table:
        # selecting between two gathers would hold two [E, d] buffers in
        # device memory
        h_both = jnp.concatenate([h_prev_old, h_prev_new], axis=0)
        view = jnp.where(e_use_new, h_prev_old.shape[0], 0)
        h_u = h_both[e_src + view]
        if model.dest_dependent:
            h_v = h_both[e_dst + view]
        else:
            # Theorem 1 requires ms_local independent of the destination for
            # unconstrained models — skip the h[dst] halo gather entirely
            # (≈2× less collective traffic at pod scale; EXPERIMENTS.md §Perf)
            h_v = jnp.zeros((e_src.shape[0], h_prev_new.shape[1]),
                            h_prev_new.dtype)
        s_u = jnp.where(e_use_new, deg_new[e_src], deg_old[e_src])
        s_v = jnp.where(e_use_new, deg_new[e_dst], deg_old[e_dst])
        ctx, raw = edge_messages(model, p, h_u, h_v, s_u, s_v, e_w, e_t)
        scale = (e_sign * e_mask.astype(raw.dtype))[:, None]
        ctx = ctx * scale
        raw = raw * scale

    # compact scatter into touched-row space (O(affected), not O(V))
    # (the narrow context column always takes XLA's segment-sum)
    with jax.named_scope("scatter"):
        d_nct = jax.ops.segment_sum(ctx, e_rowidx,
                                    num_segments=r_cap + 1)[:r_cap]
        if pallas_delta is not None:
            d_s = _pallas_delta_scatter(raw, pallas_delta, r_cap)
        else:
            d_s = jax.ops.segment_sum(raw, e_rowidx,
                                      num_segments=r_cap + 1)[:r_cap]

    # ---------------- step 2: cbn⁻¹ → delta-agg → cbn (Alg.1 l.4-6) -----
    with jax.named_scope("delta_agg"):
        nct_old_rows = nct_ext[touch_rows]
        a_rows = a_ext[touch_rows]
        nct_new_rows = nct_old_rows + d_nct
        s_rows = model.ms_cbn_inv(p, nct_old_rows, a_rows) + d_s
        a_new_rows = model.ms_cbn(p, nct_new_rows, s_rows)
        # padded rows in touch_rows all point at the scratch slot n
        a_ext = a_ext.at[touch_rows].set(a_new_rows)
        nct_ext = nct_ext.at[touch_rows].set(nct_new_rows)

    # ---------------- step 3: constrained full recompute (§IV-C) --------
    if f_rows.shape[0] > 0:
        with jax.named_scope("constrained"):
            fa, fnct, _ = subset_layer(
                model,
                p,
                h_prev_new,
                f_rows_h,
                f_mask,
                f_src,
                f_rowidx,
                f_w,
                f_t,
                f_emask,
                deg_new,
                f_cap,
            )
            a_ext = a_ext.at[f_rows].set(fa)
            nct_ext = nct_ext.at[f_rows].set(fnct)

    # ---------------- step 4: vertex-wise update (Alg.1 l.7) ------------
    with jax.named_scope("update"):
        h_prev_rows = h_prev_new[out_rows_h]
        h_rows = model.update(p, h_prev_rows, a_ext[out_rows])
        h_ext = h_ext.at[out_rows].set(h_rows)
    return a_ext, nct_ext, h_ext


@partial(jax.jit, static_argnums=(0,))
def incremental_layer(
    model: GNNModel,
    p: Params,
    h_prev_old: jax.Array,  # WITH scratch row [N+1,·]
    h_prev_new: jax.Array,
    deg_old: jax.Array,  # [N+1]
    deg_new: jax.Array,  # [N+1]
    # cached layer state (no scratch row)
    a: jax.Array,  # [N, agg]
    nct: jax.Array,  # [N, C]
    h_cur_old: jax.Array,  # [N, d_out]
    e_src: jax.Array,
    e_dst: jax.Array,
    e_rowidx: jax.Array,
    e_sign: jax.Array,
    e_use_new: jax.Array,
    e_w: jax.Array,
    e_t: jax.Array,
    e_mask: jax.Array,
    touch_rows: jax.Array,
    touch_mask: jax.Array,
    f_rows: jax.Array,
    f_mask: jax.Array,
    f_src: jax.Array,
    f_rowidx: jax.Array,
    f_w: jax.Array,
    f_t: jax.Array,
    f_emask: jax.Array,
    out_rows: jax.Array,
    out_mask: jax.Array,
    # h-space views of f_rows/out_rows: identical to the state-space arrays
    # in the in-memory engine, but differ under the compact offloaded engine
    # where h^{l-1} rows and state rows have separate compactions (§V-B)
    f_rows_h: Optional[jax.Array] = None,
    out_rows_h: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Seed per-layer API: returns (a_new [N,agg], nct_new [N,C], h_cur_new)."""
    n = a.shape[0]
    a_ext, nct_ext, h_ext = _layer_body(
        model, p, h_prev_old, h_prev_new, deg_old, deg_new,
        with_scratch(a), with_scratch(nct), with_scratch(h_cur_old),
        e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask,
        touch_rows, touch_mask,
        f_rows, f_mask, f_src, f_rowidx, f_w, f_t, f_emask,
        out_rows, out_mask,
        f_rows_h=f_rows_h, out_rows_h=out_rows_h,
    )
    return a_ext[:n], nct_ext[:n], h_ext[:n]


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3, 4, 5))
def fused_stream_step(
    model: GNNModel,
    layout: PackedLayout,
    params: Tuple[Params, ...],
    h_exts: Tuple[jax.Array, ...],  # L+1 arrays [N+1,·] — donated
    a_exts: Tuple[jax.Array, ...],  # L arrays [N+1,·] — donated
    nct_exts: Tuple[jax.Array, ...],  # L arrays [N+1,·] — donated
    idx: jax.Array,  # int32 packed buffer
    flt: jax.Array,  # float32 packed buffer (leads with deg_old/deg_new)
    msk: jax.Array,  # bool packed buffer
    feat_vals: Optional[jax.Array],  # [feat_cap, d0] when layout.feat_cap
    pallas: Optional[Tuple[Tuple[jax.Array, jax.Array, jax.Array], ...]],
) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """One fused L-layer incremental step over a packed plan.

    Returns (h_exts', a_exts', nct_exts') — the next batch's cached state,
    scratch rows re-zeroed.  One trace per PackedLayout; one dispatch per
    batch."""
    n = layout.n
    idx_sl, flt_sl, msk_sl, _ = layout_slices(layout)
    deg_old = flt[: n + 1]
    deg_new = flt[n + 1 : 2 * (n + 1)]

    h0_old = h_exts[0]
    if layout.feat_cap:
        frows = idx[: layout.feat_cap]
        fmask = msk[: layout.feat_cap]
        vals = jnp.where(fmask[:, None], feat_vals.astype(h0_old.dtype), h0_old[frows])
        h0_new = h0_old.at[frows].set(vals)  # pads → scratch, masked to no-op
    else:
        h0_new = h0_old

    h_prev_old, h_prev_new = h0_old, h0_new
    hs = [h0_new]
    as_, ncts = [], []
    for l in range(len(layout.caps)):
        gi = {name: idx[s] for name, s in idx_sl[l].items()}
        gf = {name: flt[s] for name, s in flt_sl[l].items()}
        gm = {name: msk[s] for name, s in msk_sl[l].items()}
        with jax.named_scope(f"layer{l}"):
            an, nn, hn = _layer_body(
                model, params[l], h_prev_old, h_prev_new, deg_old, deg_new,
                a_exts[l], nct_exts[l], h_exts[l + 1],
                gi["e_src"], gi["e_dst"], gi["e_rowidx"], gf["e_sign"],
                gm["e_use_new"], gf["e_w"], gi["e_t"], gm["e_mask"],
                gi["touch_rows"], gm["touch_mask"],
                gi["f_rows"], gm["f_mask"], gi["f_src"], gi["f_rowidx"],
                gf["f_w"], gi["f_t"], gm["f_emask"],
                gi["out_rows"], gm["out_mask"],
                pallas_delta=None if pallas is None else pallas[l],
            )
            # re-zero the scratch row: padded scatters may have written
            # NaN-prone values (e.g. ms_cbn_inv(0, 0)) and the state
            # persists across batches
            with jax.named_scope("update"):
                an = an.at[n].set(0.0)
                nn = nn.at[n].set(0.0)
                hn = hn.at[n].set(0.0)
        as_.append(an)
        ncts.append(nn)
        hs.append(hn)
        h_prev_old = h_exts[l + 1]
        h_prev_new = hn
    return tuple(hs), tuple(as_), tuple(ncts)


# ====================================================================== #
# Sharded fused step — the multi-device analogue of fused_stream_step
# ====================================================================== #
@lru_cache(maxsize=None)
def sharded_step_fn(model: GNNModel, mesh, axis: str):
    """Build (and cache per (model, mesh)) the jitted shard_map'd L-layer
    step over row-sharded state.

    State lives as stacked ``[S, rows_per + 1, ·]`` blocks (one scratch row
    per shard, donated).  Per layer each shard

      1. materializes its ``[halo_cap, 2·d]`` frontier buffer — under
         ``halo_mode="psum"`` by serving its slice of the replicated
         frontier row list out of its local previous-layer block and
         ``lax.psum``-ing (per-device bytes scale with the *global*
         frontier); under ``halo_mode="ppermute"`` by ``S−1`` rotation
         rounds of ``lax.ppermute`` over the plan-time per-consumer
         send/recv schedules (``ShardedPlan.comms_sh``), so each shard
         sends/receives only the halo rows its consumers actually gather —
         bitwise-equal to the psum path because psum over the one-hot
         ownership partition is a select-broadcast of the owner's exact
         bytes, and positions a shard never gathers may stay zero;
      2. concatenates ``[halo | local]`` into the workspace the plan's
         remapped indices address and runs the unmodified
         :func:`_layer_body` — all scatters are owner-local by construction
         (destination rows are never remote);
      3. re-zeroes its local scratch row.

    One trace per :class:`~repro.core.affected.ShardedLayout`; plan-side
    capacity hysteresis keeps the layout count bounded over a stream."""

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
    def step(
        slayout: ShardedLayout,
        params: Tuple[Params, ...],
        h_blocks: Tuple[jax.Array, ...],  # L+1 arrays [S, rows_per+1, ·]
        a_blocks: Tuple[jax.Array, ...],  # L arrays [S, rows_per+1, ·]
        nct_blocks: Tuple[jax.Array, ...],  # L arrays [S, rows_per+1, ·]
        idx_sh: jax.Array,  # int32  [S, idx_len]
        flt_sh: jax.Array,  # float32 [S, flt_len]
        msk_sh: jax.Array,  # bool   [S, msk_len]
        idx_rep: jax.Array,  # int32 [rep_len] replicated
        msk_rep: jax.Array,  # bool  [feat_cap] replicated
        feat_vals: jax.Array,  # [feat_cap, d0] replicated ([0, d0] if unused)
        pallas_sh=(),  # per-layer stacked (perm, dloc, brows) triples, or ()
        comms_sh=(),  # per-layer (send_pos, recv_pos) [S, S-1, pair_cap], or ()
    ):
        idx_sl, flt_sl, msk_sl, halo_sl, _ = sharded_layout_slices(slayout)
        rows_per = slayout.rows_per
        S = slayout.n_shards
        use_pallas = slayout.pallas_ecaps is not None
        use_ppermute = slayout.halo_mode == "ppermute"

        def local(prm, h_bl, a_bl, nct_bl, idx_s, flt_s, msk_s, idx_r, msk_r,
                  fvals, pal, comms):
            h_bl = [h[0] for h in h_bl]  # shard-local views [rows_per+1, ·]
            a_bl = [a[0] for a in a_bl]
            nct_bl = [c[0] for c in nct_bl]
            idx_s, flt_s, msk_s = idx_s[0], flt_s[0], msk_s[0]
            pal = tuple(tuple(x[0] for x in tr) for tr in pal)
            comms = tuple((sp_[0], rp_[0]) for sp_, rp_ in comms)
            lo = lax.axis_index(axis) * rows_per

            h0_old = h_bl[0]
            if slayout.feat_cap:
                fr = idx_r[: slayout.feat_cap]
                fm = msk_r & (fr >= lo) & (fr < lo + rows_per)
                li = jnp.where(fm, fr - lo, rows_per)  # not owned → scratch
                vals = jnp.where(fm[:, None], fvals.astype(h0_old.dtype), h0_old[li])
                h0_new = h0_old.at[li].set(vals)
            else:
                h0_new = h0_old

            h_prev_old, h_prev_new = h0_old, h0_new
            hs = [h0_new]
            as_, ncts = [], []
            for l in range(len(slayout.caps)):
                with jax.named_scope(f"layer{l}"):
                    # ---- halo exchange: frontier source rows only ----
                    with jax.named_scope("halo"):
                        d_prev = h_prev_old.shape[1]
                        halo_cap = slayout.caps[l][5]
                        if use_ppermute and S > 1:
                            # per-consumer rotation rounds: round k moves pair
                            # (owner j → consumer (j+k) mod S); send pads gather
                            # the scratch row, recv pads land in the dump row
                            # (index halo_cap, sliced off).  Positions no consumer
                            # receives stay zero — this shard never gathers them.
                            send_pos, recv_pos = comms[l]
                            buf = jnp.zeros((halo_cap + 1, 2 * d_prev),
                                            h_prev_old.dtype)
                            for k in range(1, S):
                                perm = rotation_perm(S, k)
                                sp_ = send_pos[k - 1]
                                cat = jnp.concatenate(
                                    [h_prev_old[sp_], h_prev_new[sp_]], axis=1)
                                rec = lax.ppermute(cat, axis, perm)
                                buf = buf.at[recv_pos[k - 1]].set(rec)
                            halo = buf[:halo_cap]
                        else:
                            halo_rows = idx_r[halo_sl[l]]  # global ids, pad → -1
                            own = (halo_rows >= lo) & (halo_rows < lo + rows_per)
                            pos = jnp.where(own, halo_rows - lo, rows_per)
                            cat = jnp.concatenate(
                                [h_prev_old[pos], h_prev_new[pos]], axis=1)
                            halo = lax.psum(jnp.where(own[:, None], cat, 0.0), axis)
                        ws_old = jnp.concatenate([halo[:, :d_prev], h_prev_old], axis=0)
                        ws_new = jnp.concatenate([halo[:, d_prev:], h_prev_new], axis=0)

                    gi = {k: idx_s[s] for k, s in idx_sl[l].items()}
                    gf = {k: flt_s[s] for k, s in flt_sl[l].items()}
                    gm = {k: msk_s[s] for k, s in msk_sl[l].items()}
                    an, nn, hn = _layer_body(
                        model, prm[l], ws_old, ws_new, gf["deg_old"], gf["deg_new"],
                        a_bl[l], nct_bl[l], h_bl[l + 1],
                        gi["e_src"], gi["e_dst"], gi["e_rowidx"], gf["e_sign"],
                        gm["e_use_new"], gf["e_w"], gi["e_t"], gm["e_mask"],
                        gi["touch_rows"], gm["touch_mask"],
                        gi["f_rows"], gm["f_mask"], gi["f_src"], gi["f_rowidx"],
                        gf["f_w"], gi["f_t"], gm["f_emask"],
                        gi["out_rows"], gm["out_mask"],
                        f_rows_h=gi["f_rows_h"], out_rows_h=gi["out_rows_h"],
                        pallas_delta=pal[l] if use_pallas else None,
                    )
                    an = an.at[rows_per].set(0.0)  # re-zero local scratch row
                    nn = nn.at[rows_per].set(0.0)
                    hn = hn.at[rows_per].set(0.0)
                    as_.append(an)
                    ncts.append(nn)
                    hs.append(hn)
                    h_prev_old = h_bl[l + 1]
                    h_prev_new = hn
            return (
                tuple(h[None] for h in hs),
                tuple(a[None] for a in as_),
                tuple(c[None] for c in ncts),
            )

        sh = P(axis)  # leading shard dim
        rep = P()
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(rep, sh, sh, sh, sh, sh, sh, rep, rep, rep, sh, sh),
            out_specs=(sh, sh, sh),
            check_vma=False,
        )
        return fn(params, h_blocks, a_blocks, nct_blocks, idx_sh, flt_sh, msk_sh,
                  idx_rep, msk_rep, feat_vals, pallas_sh, comms_sh)

    return step


# ====================================================================== #
# Hybrid compact layer step — the sharded-offload backend's device kernel
# ====================================================================== #
@lru_cache(maxsize=None)
def hybrid_layer_step_fn(model: GNNModel, mesh, axis: str):
    """Build (and cache per (model, mesh)) the jitted shard_map'd *compact*
    layer step for the sharded-offload hybrid.

    Every input is a stacked ``[S, cap, ·]`` staging buffer: each shard's
    slice holds only the compact ``[halo | local]`` workspace rows its plan
    touches — never the persistent state, which stays host-resident.  There
    is **no collective**: halo rows were already gathered from the owning
    shards' host blocks at staging time (since ISSUE 5 that gather runs on
    the :class:`~repro.serve.staging.HostStagingPipeline` worker, one layer
    ahead of the device), so each shard just runs the unmodified
    :func:`_layer_body` over its compact slice (one scratch row appended at
    index cap, exactly like the offloaded engine's compact views).  One
    trace per :class:`~repro.core.affected.HybridLayerLayout`.  The step is
    deliberately **not** donated: the staged buffers are double-buffered
    host views whose device copies the caller may still be shipping while
    the previous dispatch executes."""

    @partial(jax.jit, static_argnums=(0,))
    def step(
        llayout: HybridLayerLayout,
        p: Params,
        h_old_rows: jax.Array,  # [S, nh_cap, d_in] staged h^{l-1} (old view)
        h_new_rows: jax.Array,  # [S, nh_cap, d_in] staged h^{l-1} (new view)
        a_rows: jax.Array,  # [S, ns_cap, agg] staged aggregation state
        nct_rows: jax.Array,  # [S, ns_cap, C]
        h_cur_rows: jax.Array,  # [S, ns_cap, d_out]
        idx_sh: jax.Array,  # int32  [S, idx_len]
        flt_sh: jax.Array,  # float32 [S, flt_len]
        msk_sh: jax.Array,  # bool   [S, msk_len]
    ):
        idx_sl, flt_sl, msk_sl, _ = hybrid_layout_slices(llayout)
        ns_cap = llayout.caps[6]

        def local(p, h_old, h_new, a_r, nct_r, h_cur, idx_s, flt_s, msk_s):
            h_old, h_new = h_old[0], h_new[0]
            a_r, nct_r, h_cur = a_r[0], nct_r[0], h_cur[0]
            idx_s, flt_s, msk_s = idx_s[0], flt_s[0], msk_s[0]
            gi = {k: idx_s[sl] for k, sl in idx_sl.items()}
            gf = {k: flt_s[sl] for k, sl in flt_sl.items()}
            gm = {k: msk_s[sl] for k, sl in msk_sl.items()}
            an, nn, hn = _layer_body(
                model, p, with_scratch(h_old), with_scratch(h_new),
                gf["deg_old"], gf["deg_new"],
                with_scratch(a_r), with_scratch(nct_r), with_scratch(h_cur),
                gi["e_src"], gi["e_dst"], gi["e_rowidx"], gf["e_sign"],
                gm["e_use_new"], gf["e_w"], gi["e_t"], gm["e_mask"],
                gi["touch_rows"], gm["touch_mask"],
                gi["f_rows"], gm["f_mask"], gi["f_src"], gi["f_rowidx"],
                gf["f_w"], gi["f_t"], gm["f_emask"],
                gi["out_rows"], gm["out_mask"],
                f_rows_h=gi["f_rows_h"], out_rows_h=gi["out_rows_h"],
            )
            return an[None, :ns_cap], nn[None, :ns_cap], hn[None, :ns_cap]

        sh = P(axis)
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), sh, sh, sh, sh, sh, sh, sh, sh),
            out_specs=(sh, sh, sh),
            check_vma=False,
        )
        return fn(p, h_old_rows, h_new_rows, a_rows, nct_rows, h_cur_rows,
                  idx_sh, flt_sh, msk_sh)

    return step
