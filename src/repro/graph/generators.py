"""Synthetic graph generators (host side).

Real-graph stand-ins for the paper's datasets (Table III): power-law graphs
(Barabási–Albert style preferential attachment → Twitter/Friendster/Products
analogue), uniform random graphs (Erdős–Rényi), and high-average-degree dense
community graphs (Reddit analogue).  Undirected workloads are materialized as
two directed edges.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.graph.csr import CSRGraph


def _dedup(src: np.ndarray, dst: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    key = dst.astype(np.int64) * n + src.astype(np.int64)
    _, idx = np.unique(key, return_index=True)
    idx.sort()
    return src[idx], dst[idx]


def barabasi_albert(
    n: int,
    m: int = 4,
    seed: int = 0,
    undirected: bool = True,
) -> CSRGraph:
    """Preferential-attachment power-law graph with ~m edges per new vertex.

    Linear in ``n``: the attachment pool (every endpoint so far, so a
    uniform draw from it is degree-proportional) lives in one preallocated
    buffer, which grows by at most ``2m`` entries per vertex."""
    rng = np.random.default_rng(seed)
    k = max(n - m, 0)
    pool = np.empty(m + 2 * m * k, np.int64)
    pool[:m] = np.arange(m)
    size = m
    src = np.empty(m * k, np.int64)
    dst = np.empty(m * k, np.int64)
    ne = 0
    for v in range(m, n):
        # the same draws as rng.choice(pool[:size], size=m) makes
        chosen = np.unique(pool[rng.integers(0, size, size=m, dtype=np.int64)])
        c = chosen.size
        src[ne:ne + c] = v
        dst[ne:ne + c] = chosen
        ne += c
        pool[size:size + c] = chosen
        pool[size + c:size + 2 * c] = v
        size += 2 * c
    src, dst = src[:ne], dst[:ne]
    if undirected:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    mask = src != dst
    src, dst = _dedup(src[mask], dst[mask], n)
    return CSRGraph.from_edges(n, src, dst)


def erdos_renyi(n: int, avg_degree: float = 8.0, seed: int = 0, undirected: bool = False) -> CSRGraph:
    rng = np.random.default_rng(seed)
    m = int(n * avg_degree) // (2 if undirected else 1)
    src = rng.integers(0, n, size=m, dtype=np.int64)
    dst = rng.integers(0, n, size=m, dtype=np.int64)
    if undirected:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    mask = src != dst
    src, dst = _dedup(src[mask], dst[mask], n)
    return CSRGraph.from_edges(n, src, dst)


def make_graph(
    kind: str,
    n: int,
    avg_degree: float = 8.0,
    seed: int = 0,
    num_etypes: int = 1,
    weighted: bool = False,
) -> CSRGraph:
    """Unified entry: kind in {powerlaw, uniform, dense}."""
    if kind == "powerlaw":
        g = barabasi_albert(n, m=max(1, int(avg_degree) // 2), seed=seed)
    elif kind == "uniform":
        g = erdos_renyi(n, avg_degree=avg_degree, seed=seed)
    elif kind == "dense":
        g = erdos_renyi(n, avg_degree=max(avg_degree, 32.0), seed=seed)
    else:
        raise ValueError(f"unknown graph kind {kind!r}")
    rng = np.random.default_rng(seed + 1)
    src, dst, w, t = g.edges_by_dst()
    if weighted:
        w = rng.uniform(0.5, 1.5, size=src.shape[0]).astype(np.float32)
    if num_etypes > 1:
        t = rng.integers(0, num_etypes, size=src.shape[0]).astype(np.int32)
    return CSRGraph.from_edges(n, src, dst, w, t)


def random_features(
    n: int, d: int, num_labels: int = 0, seed: int = 0
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, size=(n, d)).astype(np.float32)
    y = rng.integers(0, num_labels, size=(n,)).astype(np.int32) if num_labels else None
    return x, y
