"""The Alg.-4 planner against the per-record loop it replaced.

``reference_build_plan`` below is the loop planner as it stood before
``build_plan`` became array operations over the CSR: it walks each changed
source's out-edges and each deleted edge's in-neighbourhood one vertex at a
time and emits one record at a time.  ``build_plan`` must produce the same
plan bit for bit: every ``LayerPlan`` array (values and dtype, so the same
capacities and record order), every counter, and ``deg_old`` / ``deg_new``
/ ``changed0``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np
import pytest

from repro.core.affected import BatchPlan, LayerPlan, build_plan
from repro.core.full import next_bucket
from repro.core.models import make_model
from repro.core.odec import query_cone
from repro.core.operators import GNNModel
from repro.graph.csr import CSRGraph
from repro.graph.generators import make_graph
from repro.graph.streaming import UpdateBatch

N = 2500
LAYERS = 3


# ---------------------------------------------------------------------- #
# reference: the loop planner, verbatim
# ---------------------------------------------------------------------- #
def _ref_lookup_in_edge_data(g: CSRGraph, src: np.ndarray, dst: np.ndarray):
    """Vectorized (weight, etype) lookup for existing edges (u, v)."""
    w = np.empty(src.shape[0], np.float32)
    t = np.empty(src.shape[0], np.int32)
    for i, (u, v) in enumerate(zip(src, dst)):
        nbrs, ws, ts = g.in_edge_data(int(v))
        j = np.searchsorted(nbrs, u)
        assert j < nbrs.shape[0] and nbrs[j] == u, f"edge ({u},{v}) missing"
        w[i] = ws[j]
        t[i] = ts[j]
    return w, t


def _ref_pad_records(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    sign: np.ndarray,
    use_new: np.ndarray,
    w: np.ndarray,
    t: np.ndarray,
) -> Tuple[np.ndarray, ...]:
    e = src.shape[0]
    e_cap = next_bucket(e)
    rows, rowinv = np.unique(dst, return_inverse=True) if e else (np.zeros(0, np.int64), np.zeros(0, np.int64))
    r_cap = next_bucket(rows.shape[0])

    def pad(a, cap, fill, dt):
        out = np.full(cap, fill, dtype=dt)
        out[: a.shape[0]] = a
        return out

    return (
        pad(src, e_cap, n, np.int32),
        pad(dst, e_cap, n, np.int32),
        pad(rowinv, e_cap, r_cap, np.int32),
        pad(sign, e_cap, 0.0, np.float32),
        pad(use_new, e_cap, False, bool),
        pad(w, e_cap, 0.0, np.float32),
        pad(t, e_cap, 0, np.int32),
        pad(np.ones(e, bool), e_cap, False, bool),
        pad(rows, r_cap, n, np.int32),
        pad(np.ones(rows.shape[0], bool), r_cap, False, bool),
    )


def reference_build_plan(
    model: GNNModel,
    g_old: CSRGraph,
    g_new: CSRGraph,
    batch: UpdateBatch,
    num_layers: int,
    restrict: Optional[List[set]] = None,
) -> BatchPlan:
    """Build per-layer incremental plans.

    ``restrict`` (ODEC, paper §V-D): optional per-layer vertex sets; layer
    l's work is intersected with ``restrict[l]`` (the query-induced K-hop
    cone), turning RTEC into on-demand embedding computation."""
    n = g_old.n
    deg_old = g_old.in_degree().astype(np.float32)
    deg_new = g_new.in_degree().astype(np.float32)
    deg_changed = np.nonzero(deg_old != deg_new)[0]

    ins_s = np.asarray(batch.ins_src, np.int64)
    ins_d = np.asarray(batch.ins_dst, np.int64)
    ins_w = (
        np.asarray(batch.ins_weights, np.float32)
        if batch.ins_weights is not None
        else np.ones(ins_s.shape[0], np.float32)
    )
    ins_t = (
        np.asarray(batch.ins_etypes, np.int32)
        if batch.ins_etypes is not None
        else np.zeros(ins_s.shape[0], np.int32)
    )
    del_s = np.asarray(batch.del_src, np.int64)
    del_d = np.asarray(batch.del_dst, np.int64)
    if del_s.size:
        del_w, del_t = _ref_lookup_in_edge_data(g_old, del_s, del_d)
    else:
        del_w = np.zeros(0, np.float32)
        del_t = np.zeros(0, np.int32)
    inserted_keys = set(zip(ins_s.tolist(), ins_d.tolist()))

    changed0 = (
        np.asarray(batch.feat_vertices, np.int64)
        if batch.feat_vertices is not None
        else np.zeros(0, np.int64)
    )
    changed_h = changed0  # vertices whose h^{l-1} changed
    deg_new_int = g_new.in_degree()

    plans: List[LayerPlan] = []
    for layer_idx in range(num_layers):
        allowed = restrict[layer_idx] if restrict is not None else None
        changed_set = set(changed_h.tolist())
        # sources whose outgoing contributions changed
        c_src = set(changed_set)
        if model.src_struct_dependent:
            c_src |= set(deg_changed.tolist())
        # constrained full-recompute destinations
        if model.dest_dependent:
            v_full = np.array(
                sorted(
                    v
                    for v in changed_set
                    if deg_new_int[v] > 0 and (allowed is None or v in allowed)
                ),
                np.int64,
            )
        else:
            v_full = np.zeros(0, np.int64)
        v_full_set = set(v_full.tolist())

        # ---- incremental records ----
        rs, rd, rsign, rnew, rw, rt = [], [], [], [], [], []
        n_changed_edges = 0

        def _emit(s, d, sign, usenew, w, t):
            rs.append(s)
            rd.append(d)
            rsign.append(sign)
            rnew.append(usenew)
            rw.append(w)
            rt.append(t)

        def _allowed(d: int) -> bool:
            return allowed is None or d in allowed

        for i in range(ins_s.shape[0]):
            if int(ins_d[i]) not in v_full_set and _allowed(int(ins_d[i])):
                _emit(ins_s[i], ins_d[i], 1.0, True, ins_w[i], ins_t[i])
        for i in range(del_s.shape[0]):
            if int(del_d[i]) not in v_full_set and _allowed(int(del_d[i])):
                _emit(del_s[i], del_d[i], -1.0, False, del_w[i], del_t[i])
        for u in sorted(c_src):
            nbrs, ws, ts = g_new.out_edge_data(int(u))
            for j in range(nbrs.shape[0]):
                d = int(nbrs[j])
                if (int(u), d) in inserted_keys or d in v_full_set or not _allowed(d):
                    continue
                _emit(u, d, -1.0, False, ws[j], ts[j])
                _emit(u, d, 1.0, True, ws[j], ts[j])
                n_changed_edges += 1

        rec = _ref_pad_records(
            n,
            np.array(rs, np.int64),
            np.array(rd, np.int64),
            np.array(rsign, np.float32),
            np.array(rnew, bool),
            np.array(rw, np.float32),
            np.array(rt, np.int32),
        )
        (e_src, e_dst, e_rowidx, e_sign, e_use_new, e_w, e_t, e_mask, touch_rows, touch_mask) = rec

        # ---- constrained full path ----
        f_srcs, f_ridx, f_ws, f_ts = [], [], [], []
        for ri, v in enumerate(v_full):
            nbrs, ws, ts = g_new.in_edge_data(int(v))
            f_srcs.extend(nbrs.tolist())
            f_ridx.extend([ri] * nbrs.shape[0])
            f_ws.extend(ws.tolist())
            f_ts.extend(ts.tolist())
        f_cap = next_bucket(v_full.shape[0])
        fe_cap = next_bucket(len(f_srcs))

        def padv(a, cap, fill, dt):
            out = np.full(cap, fill, dtype=dt)
            out[: len(a)] = a
            return out

        f_rows = padv(v_full, f_cap, n, np.int32)
        f_mask = padv(np.ones(v_full.shape[0], bool), f_cap, False, bool)
        f_src = padv(f_srcs, fe_cap, n, np.int32)
        f_rowidx = padv(f_ridx, fe_cap, f_cap, np.int32)
        f_w = padv(f_ws, fe_cap, 0.0, np.float32)
        f_t = padv(f_ts, fe_cap, 0, np.int32)
        f_emask = padv(np.ones(len(f_srcs), bool), fe_cap, False, bool)

        # ---- output rows ----
        out_set = set(touch_rows[touch_mask].tolist()) | v_full_set
        if model.update_uses_h:
            out_set |= changed_set if allowed is None else (changed_set & allowed)
        out = np.array(sorted(out_set), np.int64)
        o_cap = next_bucket(out.shape[0])
        out_rows = padv(out, o_cap, n, np.int32)
        out_mask = padv(np.ones(out.shape[0], bool), o_cap, False, bool)

        n_inc = ins_s.shape[0] + del_s.shape[0] + n_changed_edges
        srcs_accessed = len(set(rs) | set(f_srcs))
        plans.append(
            LayerPlan(
                e_src=e_src,
                e_dst=e_dst,
                e_rowidx=e_rowidx,
                e_sign=e_sign,
                e_use_new=e_use_new,
                e_w=e_w,
                e_t=e_t,
                e_mask=e_mask,
                touch_rows=touch_rows,
                touch_mask=touch_mask,
                f_rows=f_rows,
                f_mask=f_mask,
                f_src=f_src,
                f_rowidx=f_rowidx,
                f_w=f_w,
                f_t=f_t,
                f_emask=f_emask,
                out_rows=out_rows,
                out_mask=out_mask,
                n_inc_edges=n_inc,
                n_full_edges=len(f_srcs),
                n_touch_rows=int(touch_mask.sum()),
                n_full_rows=int(v_full.shape[0]),
                n_out_rows=int(out.shape[0]),
                n_src_accessed=srcs_accessed,
            )
        )
        changed_h = out

    deg_old_x = np.concatenate([deg_old, np.zeros(1, np.float32)])
    deg_new_x = np.concatenate([deg_new, np.zeros(1, np.float32)])
    return BatchPlan(layers=plans, deg_old=deg_old_x, deg_new=deg_new_x, changed0=changed0)


# ---------------------------------------------------------------------- #
# inputs
# ---------------------------------------------------------------------- #
def _graph(seed: int) -> CSRGraph:
    return make_graph("powerlaw", N, avg_degree=8, seed=seed, num_etypes=3, weighted=True)


def _batch(g: CSRGraph, seed: int, n_ins: int, n_del: int, n_feat: int,
           feat_on_ins_src: bool = False) -> UpdateBatch:
    """Undirected inserts of absent edges, undirected deletes of present
    ones, and feature rewrites; weights and edge types on the inserts."""
    rng = np.random.default_rng(seed)
    src, dst, _, _ = g.edges_by_dst()
    live = set(zip(src.tolist(), dst.tolist()))
    ins: List[Tuple[int, int]] = []
    while len(ins) < 2 * n_ins:
        a, b = (int(x) for x in rng.integers(0, g.n, 2))
        if a != b and (a, b) not in live and (a, b) not in ins:
            ins += [(a, b), (b, a)]
    dels: List[Tuple[int, int]] = []
    for k in rng.permutation(src.shape[0]):
        if len(dels) == 2 * n_del:
            break
        a, b = int(src[k]), int(dst[k])
        if (a, b) not in dels and (b, a) in live:
            dels += [(a, b), (b, a)]
    feat = rng.choice(g.n, n_feat, replace=False).astype(np.int64)
    if feat_on_ins_src:
        feat = np.unique(np.concatenate([feat, [ins[0][0], ins[2][0]]])).astype(np.int64)
    ia = np.array(ins, np.int64).reshape(-1, 2)
    da = np.array(dels, np.int64).reshape(-1, 2)
    return UpdateBatch(
        ins_src=ia[:, 0], ins_dst=ia[:, 1], del_src=da[:, 0], del_dst=da[:, 1],
        ins_weights=rng.uniform(0.5, 1.5, ia.shape[0]).astype(np.float32),
        ins_etypes=rng.integers(0, 3, ia.shape[0]).astype(np.int32),
        feat_vertices=feat if feat.size else None,
    )


def _apply(g: CSRGraph, b: UpdateBatch) -> CSRGraph:
    return g.apply_updates(b.ins_src, b.ins_dst, b.del_src, b.del_dst,
                           b.ins_weights, b.ins_etypes)


def assert_same_plan(got: BatchPlan, want: BatchPlan) -> None:
    assert len(got.layers) == len(want.layers)
    for l, (g, w) in enumerate(zip(got.layers, want.layers)):
        for f in dataclasses.fields(LayerPlan):
            a, b = getattr(g, f.name), getattr(w, f.name)
            if isinstance(b, np.ndarray):
                assert a.dtype == b.dtype, (l, f.name, a.dtype, b.dtype)
                assert np.array_equal(a, b), (l, f.name)
            else:
                assert type(a) is type(b) and a == b, (l, f.name, a, b)
    for name in ("deg_old", "deg_new", "changed0"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name


# (model, batch shape, query-cone size or None); batch shape is
# (inserts, deletes, feature rewrites, a feature rewrite on an insert's source)
CASES = {
    "gcn-mixed": ("gcn", (12, 6, 4, False), None),
    "sage-mixed": ("sage", (12, 6, 4, False), None),
    "gat-mixed": ("gat", (12, 6, 4, False), None),
    "rgcn-mixed": ("rgcn", (12, 6, 4, False), None),
    "agnn-mixed": ("agnn", (8, 8, 2, False), None),
    "gcn-insert-src-changed": ("gcn", (10, 0, 0, True), None),
    "sage-insert-src-changed": ("sage", (10, 2, 0, True), None),
    "gat-insert-src-changed": ("gat", (10, 2, 0, True), None),
    "gcn-feature-only": ("gcn", (0, 0, 6, False), None),
    "gat-feature-only": ("gat", (0, 0, 6, False), None),
    "sage-empty": ("sage", (0, 0, 0, False), None),
    "gcn-empty": ("gcn", (0, 0, 0, False), None),
    "rgcn-delete-only": ("rgcn", (0, 10, 0, False), None),
    "gcn-odec": ("gcn", (12, 6, 4, False), 40),
    "sage-odec": ("sage", (12, 6, 4, False), 40),
    "gat-odec": ("gat", (12, 6, 4, True), 40),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_build_plan_matches_loop_reference(case):
    name, (n_ins, n_del, n_feat, on_src), cone = CASES[case]
    seed = sorted(CASES).index(case)
    g = _graph(seed)
    b = _batch(g, seed + 100, n_ins, n_del, n_feat, feat_on_ins_src=on_src)
    if on_src:
        # the inserted-edge filter has work: an insert leaves a changed source
        assert np.isin(b.ins_src, b.feat_vertices).any()
    g_new = _apply(g, b)
    restrict = None
    if cone is not None:
        q = np.random.default_rng(seed).choice(N, cone, replace=False)
        restrict = query_cone(g_new, q, LAYERS)
    model = make_model(name)
    want = reference_build_plan(model, g, g_new, b, LAYERS, restrict=restrict)
    got = build_plan(model, g, g_new, b, LAYERS, restrict=restrict)
    assert_same_plan(got, want)
    if n_ins + n_del + n_feat:
        assert want.total_inc_edges() > 0


@pytest.mark.parametrize("name", ["gcn", "gat"])
def test_build_plan_walks_no_vertex_at_a_time(monkeypatch, name):
    """The planner reads the CSR arrays whole: with the per-vertex accessors
    disabled it still builds the same plan."""
    g = _graph(1)
    b = _batch(g, 7, 8, 4, 3, feat_on_ins_src=True)
    g_new = _apply(g, b)
    model = make_model(name)
    want = reference_build_plan(model, g, g_new, b, LAYERS)

    def per_vertex(self, v):
        raise AssertionError("build_plan walked the graph one vertex at a time")

    monkeypatch.setattr(CSRGraph, "out_edge_data", per_vertex)
    monkeypatch.setattr(CSRGraph, "in_edge_data", per_vertex)
    assert_same_plan(build_plan(model, g, g_new, b, LAYERS), want)


def test_build_plan_rejects_delete_of_missing_edge():
    g = _graph(2)
    src, dst, _, _ = g.edges_by_dst()
    absent = next(v for v in range(g.n) if not g.has_edge(int(src[0]), v) and v != src[0])
    b = UpdateBatch(ins_src=np.zeros(0, np.int64), ins_dst=np.zeros(0, np.int64),
                    del_src=np.array([src[1], src[0]], np.int64),
                    del_dst=np.array([dst[1], absent], np.int64))
    with pytest.raises(ValueError, match="missing"):
        build_plan(make_model("gcn"), g, g, b, LAYERS)
