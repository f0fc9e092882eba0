"""The deployment's base graph, generated once per checkout and cached.

A configuration's ``graph`` block names a generator and its parameters,
with a fixed seed of its own: the graph is the deployment's dataset, not an
input of one run.  The preferential-attachment generator is copied from the
program (``repro.graph.generators.barabasi_albert``, linear form) so that a
change to the program cannot move the benchmark's data.  It returns the
undirected edges in creation order: the newest edges are the held-out
insert pool, as the paper's protocol reserves the most recent edges.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path

import numpy as np


def barabasi_albert_pairs(n: int, m: int, seed: int) -> np.ndarray:
    """Undirected preferential-attachment edges ``[k, 2]`` (older vertex
    first) in creation order: vertex ``v`` attaches to up to ``m`` distinct
    earlier vertices drawn in proportion to their degree."""
    rng = np.random.default_rng(seed)
    k = max(n - m, 0)
    pool = np.empty(m + 2 * m * k, np.int64)
    pool[:m] = np.arange(m)
    size = m
    old = np.empty(m * k, np.int64)
    new = np.empty(m * k, np.int64)
    ne = 0
    for v in range(m, n):
        chosen = np.unique(pool[rng.integers(0, size, size=m, dtype=np.int64)])
        c = chosen.size
        old[ne:ne + c] = chosen
        new[ne:ne + c] = v
        ne += c
        pool[size:size + c] = chosen
        pool[size + c:size + 2 * c] = v
        size += 2 * c
    return np.stack([old[:ne], new[:ne]], axis=1).astype(np.int32)


@dataclasses.dataclass
class Dataset:
    """Undirected edge list of the deployment: ``pairs[:num_base]`` are the
    base graph, ``pairs[num_base:]`` the insert pool, oldest first."""

    n: int
    pairs: np.ndarray  # [U, 2] int32
    num_base: int

    @property
    def num_pool(self) -> int:
        return int(self.pairs.shape[0] - self.num_base)

    def directed(self):
        """Master directed edge list: undirected id ``k`` is directed ids
        ``k`` (a→b) and ``k + U`` (b→a)."""
        a, b = self.pairs[:, 0], self.pairs[:, 1]
        return np.concatenate([a, b]), np.concatenate([b, a])


def _cache_key(graph: dict) -> str:
    blob = json.dumps(graph, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def load_dataset(graph: dict, cache_dir: os.PathLike | None) -> Dataset:
    """Build (or read back) the configuration's graph.  ``graph`` holds
    ``generator``, ``n``, the generator's parameters, ``seed`` and
    ``pool_edges`` (how many of the newest undirected edges are held out)."""
    if graph["generator"] != "barabasi_albert":
        raise ValueError(f"unknown graph generator {graph['generator']!r}")
    path = None
    if cache_dir is not None:
        path = Path(cache_dir) / f"{graph['generator']}-{_cache_key(graph)}.npy"
        if path.exists():
            pairs = np.load(path)
            return Dataset(graph["n"], pairs, pairs.shape[0] - graph["pool_edges"])
    pairs = barabasi_albert_pairs(graph["n"], graph["m"], graph["seed"])
    if graph["pool_edges"] >= pairs.shape[0]:
        raise ValueError("pool_edges must leave a base graph")
    if path is not None:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".part")
        with open(tmp, "wb") as f:
            np.save(f, pairs)
        os.replace(tmp, path)
    return Dataset(graph["n"], pairs, pairs.shape[0] - graph["pool_edges"])
