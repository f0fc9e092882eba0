"""Graph substrate: CSR, PMA dynamic CSR, generators, update streams."""
import numpy as np
import pytest

from repro.graph import CSRGraph, PMAGraph, make_graph, make_stream
from repro.graph.generators import barabasi_albert, erdos_renyi


def test_csr_roundtrip():
    src = np.array([0, 1, 2, 3, 1])
    dst = np.array([2, 2, 3, 4, 4])
    g = CSRGraph.from_edges(5, src, dst)
    assert g.num_edges == 5
    assert set(g.in_neighbors(2).tolist()) == {0, 1}
    assert set(g.out_neighbors(1).tolist()) == {2, 4}
    assert g.has_edge(0, 2) and not g.has_edge(2, 0)
    np.testing.assert_array_equal(g.in_degree(), [0, 0, 2, 1, 2])
    np.testing.assert_array_equal(g.out_degree(), [1, 2, 1, 1, 0])


def test_csr_duplicate_rejected():
    with pytest.raises(ValueError):
        CSRGraph.from_edges(3, np.array([0, 0]), np.array([1, 1]))


def test_csr_apply_updates():
    g = CSRGraph.from_edges(4, np.array([0, 1]), np.array([1, 2]))
    g2 = g.apply_updates(
        np.array([2]), np.array([3]), np.array([0]), np.array([1])
    )
    assert g2.has_edge(2, 3) and not g2.has_edge(0, 1)
    assert g.has_edge(0, 1), "original snapshot must be immutable"
    with pytest.raises(ValueError):
        g.apply_updates(np.array([], np.int64), np.array([], np.int64), np.array([3]), np.array([0]))


def test_csr_edge_data_alignment():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 50, 200)
    dst = rng.integers(0, 50, 200)
    mask = src != dst
    src, dst = src[mask], dst[mask]
    key = dst * 50 + src
    _, idx = np.unique(key, return_index=True)
    src, dst = src[idx], dst[idx]
    w = rng.uniform(0, 1, src.shape[0]).astype(np.float32)
    t = rng.integers(0, 3, src.shape[0]).astype(np.int32)
    g = CSRGraph.from_edges(50, src, dst, w, t)
    # in- and out-views must agree per edge
    for v in range(50):
        nbrs, ws, ts = g.in_edge_data(v)
        for u, wi, ti in zip(nbrs, ws, ts):
            outs, wo, to = g.out_edge_data(int(u))
            j = np.nonzero(outs == v)[0]
            assert j.size == 1
            assert wo[j[0]] == wi and to[j[0]] == ti


def test_pma_insert_delete_snapshot():
    pma = PMAGraph(20, capacity=64, seg=16)
    rng = np.random.default_rng(1)
    edges = set()
    for _ in range(300):
        u, v = int(rng.integers(20)), int(rng.integers(20))
        if (u, v) in edges:
            pma.delete_edge(u, v)
            edges.discard((u, v))
        else:
            pma.insert_edge(u, v, w=0.5, t=1)
            edges.add((u, v))
    snap = pma.snapshot()
    assert snap.num_edges == len(edges)
    for (u, v) in edges:
        assert snap.has_edge(u, v)
    assert pma.num_edges == len(edges)


def test_pma_growth_preserves_edges():
    pma = PMAGraph(5, capacity=8, seg=8)
    edges = [(i % 5, (i * 3 + 1) % 5) for i in range(20)]
    edges = list(dict.fromkeys((u, v) for u, v in edges if u != v))
    for u, v in edges:
        pma.insert_edge(u, v)
    snap = pma.snapshot()
    for u, v in edges:
        assert snap.has_edge(u, v)


def test_pma_errors():
    pma = PMAGraph(4)
    pma.insert_edge(0, 1)
    with pytest.raises(ValueError):
        pma.insert_edge(0, 1)
    with pytest.raises(ValueError):
        pma.delete_edge(1, 0)


def test_generators_shapes():
    g = barabasi_albert(300, m=3, seed=0)
    assert g.n == 300 and g.num_edges > 300
    # power-law-ish: max degree much larger than mean
    deg = g.in_degree()
    assert deg.max() > 4 * deg.mean()
    g2 = erdos_renyi(200, avg_degree=6.0, seed=1)
    assert abs(g2.num_edges / 200 - 6.0) < 2.0


def _barabasi_albert_list_loop(n, m, seed):
    """The original quadratic generator: rng.choice over a growing list."""
    rng = np.random.default_rng(seed)
    repeated = list(range(m))
    src, dst = [], []
    for v in range(m, n):
        chosen = np.unique(rng.choice(repeated, size=m, replace=True))
        src += [v] * len(chosen)
        dst += chosen.tolist()
        repeated += chosen.tolist() + [v] * len(chosen)
    src, dst = np.array(src, np.int64), np.array(dst, np.int64)
    return np.concatenate([src, dst]), np.concatenate([dst, src])


@pytest.mark.parametrize("n,m,seed", [(300, 4, 0), (400, 7, 3)])
def test_barabasi_albert_matches_list_loop(n, m, seed):
    """The preallocated-buffer generator draws exactly what the list loop
    drew, so every seeded graph (and every counter gate built on one) is
    unchanged."""
    src, dst = _barabasi_albert_list_loop(n, m, seed)
    ref = CSRGraph.from_edges(n, src, dst)
    got = barabasi_albert(n, m=m, seed=seed)
    for a, b in zip(ref.edges_by_dst(), got.edges_by_dst()):
        np.testing.assert_array_equal(a, b)


def test_stream_consistency():
    g = make_graph("powerlaw", 200, avg_degree=6, seed=0)
    wl = make_stream(g, num_batches=5, batch_edges=20, delete_frac=0.3, seed=2)
    cur = wl.base
    for b in wl.batches:
        # applying every batch must be legal (no dup inserts / missing deletes)
        cur = cur.apply_updates(b.ins_src, b.ins_dst, b.del_src, b.del_dst,
                                b.ins_weights, b.ins_etypes)
        assert b.num_updates > 0
    assert cur.num_edges >= wl.base.num_edges - sum(b.del_src.size for b in wl.batches)
