"""One general generator for every traffic mix.

A mix is a JSON file of parameters (``mixes/<name>.json``):

``update_rate_per_s``, ``insert_share``, ``delete_share``, ``feature_share``
    Poisson arrivals of update events and their kinds.  An insert takes the
    oldest edge left in the held-out pool; a delete takes an edge drawn
    uniformly from the live edges (so its endpoints are biased by degree); a
    feature rewrite gives a uniform vertex a fresh row.  An edge event
    carries both directions of the undirected edge.
``read_rate_per_s``, ``read_rows``, ``read_zipf_s``, ``pinned_share``
    Poisson arrivals of reads.  A read asks for ``read_rows`` distinct rows
    drawn by Zipf weights over a seed-permuted vertex order; a pinned read
    asks for the version before the current one.
``batch_cap_events``
    The most events one update batch takes.
``warmup``
    Fixed warm-up batches, each ``{"events": k, "features": bool}``, drawn
    from the configuration's graph seed so that set-up is the same work in
    every run.

Everything a run sends is drawn here before the window opens.  The
arrival gaps, the kinds of the events and which reads are pinned are one
fixed multiset per mix and window length, drawn from the configuration's
graph seed; a run's seed orders them, and draws the deleted edges, the
rewritten vertices, their rows, and the read rows.  So every seed sends
the same amount of work, arriving in another order, as a Poisson process.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

INSERT, DELETE, FEATURE = 0, 1, 2


class LiveEdges:
    """The live undirected edge ids with O(1) uniform draw and removal, and
    the insert pool's cursor.  Shared by warm-up and window generation so
    the two see one consistent graph."""

    def __init__(self, num_base: int, num_total: int):
        self.ids = np.empty(num_total, np.int64)
        self.ids[:num_base] = np.arange(num_base)
        self.pos = np.full(num_total, -1, np.int64)
        self.pos[:num_base] = np.arange(num_base)
        self.size = num_base
        self.next_pool = num_base
        self.num_total = num_total

    def insert(self) -> int:
        if self.next_pool >= self.num_total:
            raise RuntimeError("the held-out insert pool is exhausted")
        e = self.next_pool
        self.next_pool += 1
        self.ids[self.size] = e
        self.pos[e] = self.size
        self.size += 1
        return e

    def delete(self, u: float) -> int:
        """Remove and return the live edge at uniform draw ``u`` in [0, 1)."""
        j = int(u * self.size)
        e = int(self.ids[j])
        last = int(self.ids[self.size - 1])
        self.ids[j] = last
        self.pos[last] = j
        self.pos[e] = -1
        self.size -= 1
        return e


@dataclasses.dataclass
class Events:
    """Update events in arrival order.  ``edge`` is the undirected edge id
    (−1 for a feature rewrite), ``vertex`` the rewritten vertex (−1 for an
    edge event), ``feat`` the index of its row in ``values``."""

    due: np.ndarray  # float64 seconds from the window's opening (NaN: warm-up)
    kind: np.ndarray  # int8
    edge: np.ndarray  # int64
    vertex: np.ndarray  # int64
    feat: np.ndarray  # int64
    values: np.ndarray  # [num_feature_events, d] float32

    def __len__(self) -> int:
        return int(self.kind.shape[0])


def draw_kinds(rng: np.random.Generator, count: int, shares) -> np.ndarray:
    """``count`` event kinds with probabilities ``shares`` (insert, delete,
    feature)."""
    p = np.asarray(shares, np.float64)
    return rng.choice(3, size=count, p=p / p.sum()).astype(np.int8)


def make_events(rng: np.random.Generator, live: LiveEdges, kind: np.ndarray,
                n: int, feat_dim: int,
                due: Optional[np.ndarray] = None) -> Events:
    """Events of the given kinds, their edges drawn and applied to ``live``
    in order."""
    count = kind.shape[0]
    draws = rng.random(count)
    verts = rng.integers(0, n, size=count)
    nfeat = int((kind == FEATURE).sum())
    values = rng.standard_normal((nfeat, feat_dim), dtype=np.float32)
    edge = np.full(count, -1, np.int64)
    vertex = np.full(count, -1, np.int64)
    feat = np.full(count, -1, np.int64)
    f = 0
    for i in range(count):
        k = kind[i]
        if k == INSERT:
            edge[i] = live.insert()
        elif k == DELETE:
            edge[i] = live.delete(draws[i])
        else:
            vertex[i] = verts[i]
            feat[i] = f
            f += 1
    if due is None:
        due = np.full(count, np.nan)
    return Events(due, kind, edge, vertex, feat, values)


def concat_events(parts: List[Events]) -> Events:
    off = np.cumsum([0] + [p.values.shape[0] for p in parts])[:-1]
    return Events(
        due=np.concatenate([p.due for p in parts]),
        kind=np.concatenate([p.kind for p in parts]),
        edge=np.concatenate([p.edge for p in parts]),
        vertex=np.concatenate([p.vertex for p in parts]),
        feat=np.concatenate([np.where(p.feat >= 0, p.feat + o, -1)
                             for p, o in zip(parts, off)]),
        values=np.concatenate([p.values for p in parts]),
    )


def arrival_gaps(rng: np.random.Generator, rate: float,
                 seconds: float) -> np.ndarray:
    """The gaps before each arrival in [0, seconds) of a Poisson process at
    ``rate``: their sum stays under ``seconds`` in any order."""
    if rate <= 0:
        return np.zeros(0)
    out, t = [], 0.0
    while t < seconds:
        gaps = rng.exponential(1.0 / rate, size=max(16, int(rate * seconds * 1.2)))
        out.append(gaps)
        t += float(gaps.sum())
    gaps = np.concatenate(out)
    return gaps[:int(np.searchsorted(np.cumsum(gaps), seconds))]


@dataclasses.dataclass
class Reads:
    due: np.ndarray  # float64 seconds from the window's opening
    rows: np.ndarray  # [num_reads, read_rows] int64, distinct within a read
    pinned: np.ndarray  # bool: asks for the version before the current one


def zipf_cdf(n: int, s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def make_reads(shape: np.random.Generator, rng: np.random.Generator,
               mix: dict, n: int, seconds: float) -> Reads:
    """Reads from the fixed ``shape`` draws, ordered and filled by ``rng``."""
    gaps = arrival_gaps(shape, mix["read_rate_per_s"], seconds)
    pinned = shape.random(gaps.shape[0]) < mix["pinned_share"]
    order = rng.permutation(gaps.shape[0])
    due, pinned = np.cumsum(gaps[order]), pinned[order]
    k = int(mix["read_rows"])
    if k > n:
        raise ValueError("read_rows exceeds the vertex count")
    ranked = rng.permutation(n)  # vertex of each Zipf rank
    cdf = zipf_cdf(n, mix["read_zipf_s"])
    rows = np.empty((due.shape[0], k), np.int64)
    for r in range(due.shape[0]):
        # successive sampling without replacement: the first k distinct
        # ranks of a stream of Zipf draws, in the order they appear
        got = np.zeros(0, np.int64)
        while got.shape[0] < k:
            draw = np.searchsorted(cdf, rng.random(4 * k), side="right")
            cand = np.concatenate([got, np.minimum(draw, n - 1)])
            _, first = np.unique(cand, return_index=True)
            got = cand[np.sort(first)]
        rows[r] = ranked[got[:k]]
    return Reads(due, rows, pinned)


def shares(mix: dict):
    return (mix["insert_share"], mix["delete_share"], mix["feature_share"])


def make_window_events(shape: np.random.Generator, rng: np.random.Generator,
                       live: LiveEdges, mix: dict, n: int, feat_dim: int,
                       seconds: float) -> Events:
    """Update events from the fixed ``shape`` draws (gaps and kinds),
    ordered and filled by ``rng``."""
    gaps = arrival_gaps(shape, mix["update_rate_per_s"], seconds)
    kind = draw_kinds(shape, gaps.shape[0], shares(mix))
    order = rng.permutation(gaps.shape[0])
    return make_events(rng, live, kind[order], n, feat_dim,
                       np.cumsum(gaps[order]))


def make_warmup(rng: np.random.Generator, live: LiveEdges, mix: dict, n: int,
                feat_dim: int) -> List[Events]:
    out = []
    for spec in mix["warmup"]:
        sh = list(shares(mix))
        if not spec["features"]:
            sh[FEATURE] = 0.0
        out.append(make_events(rng, live, draw_kinds(rng, int(spec["events"]), sh),
                               n, feat_dim))
    return out


@dataclasses.dataclass
class Batch:
    """One update batch as the program receives it, with the events it
    carries (``lo:hi`` into the run's event log)."""

    lo: int
    hi: int
    ins_src: np.ndarray
    ins_dst: np.ndarray
    del_src: np.ndarray
    del_dst: np.ndarray
    feat_vertices: Optional[np.ndarray]
    feat_values: Optional[np.ndarray]


def build_batch(ev: Events, lo: int, hi: int, pairs: np.ndarray) -> Batch:
    """Net effect of events ``lo:hi``: an edge inserted and deleted inside
    the batch is in neither list, and a vertex rewritten twice keeps its
    last row."""
    kind, edge = ev.kind[lo:hi], ev.edge[lo:hi]
    ins = edge[kind == INSERT]
    dele = edge[kind == DELETE]
    both = np.intersect1d(ins, dele)
    ins = np.setdiff1d(ins, both)
    dele = np.setdiff1d(dele, both)

    def directed(ids):
        a = pairs[ids, 0].astype(np.int64)
        b = pairs[ids, 1].astype(np.int64)
        return np.concatenate([a, b]), np.concatenate([b, a])

    ins_src, ins_dst = directed(ins)
    del_src, del_dst = directed(dele)
    fi = np.nonzero(kind == FEATURE)[0] + lo
    fv = fvals = None
    if fi.size:
        verts = ev.vertex[fi]
        # last occurrence of each vertex wins
        _, last = np.unique(verts[::-1], return_index=True)
        keep = fi[::-1][last]
        fv = ev.vertex[keep]
        fvals = ev.values[ev.feat[keep]]
    return Batch(lo, hi, ins_src, ins_dst, del_src, del_dst, fv, fvals)
