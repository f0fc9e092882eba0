"""The benchmark's generators: graph, update events, reads, batches."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import traffic as tr  # noqa: E402
from chipbench.graphs import barabasi_albert_pairs, load_dataset  # noqa: E402

MIX = json.loads((ROOT / "chipbench" / "mixes" / "live14.json").read_text())
GRAPH = {"generator": "barabasi_albert", "n": 2000, "m": 4, "seed": 3,
         "pool_edges": 3000}


@pytest.fixture(scope="module")
def ds():
    return load_dataset(GRAPH, None)


def _events(ds, seed, count=1500, shape_seed=0):
    live = tr.LiveEdges(ds.num_base, ds.pairs.shape[0])
    seconds = count / MIX["update_rate_per_s"]
    return tr.make_window_events(np.random.default_rng(shape_seed),
                                 np.random.default_rng(seed), live, MIX, ds.n,
                                 8, seconds)


def test_graph_is_simple_and_held_out_edges_are_the_newest(ds):
    p = ds.pairs.astype(np.int64)
    assert (p[:, 0] < p[:, 1]).all()  # older vertex first, no self loops
    assert np.unique(p[:, 0] * ds.n + p[:, 1]).size == p.shape[0]
    assert ds.num_pool == GRAPH["pool_edges"]
    # creation order: the newer endpoint never decreases
    assert (np.diff(p[:, 1]) >= 0).all()
    assert (barabasi_albert_pairs(2000, 4, 3) == ds.pairs).all()


def test_graph_cache_round_trips(tmp_path):
    a = load_dataset(GRAPH, tmp_path)
    b = load_dataset(GRAPH, tmp_path)  # read back from the cache
    assert len(list(tmp_path.iterdir())) == 1
    assert (a.pairs == b.pairs).all() and a.num_base == b.num_base


def test_events_are_deterministic_per_seed(ds):
    a, b, c = _events(ds, 5), _events(ds, 5), _events(ds, 6)
    for f in ("due", "kind", "edge", "vertex", "values"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.due, c.due)
    assert not np.array_equal(a.edge, c.edge)


def test_every_seed_sends_the_same_work_in_another_order(ds):
    a, c = _events(ds, 5), _events(ds, 6)
    assert len(a) == len(c)
    np.testing.assert_allclose(np.sort(np.diff(np.r_[0.0, a.due])),
                               np.sort(np.diff(np.r_[0.0, c.due])))
    np.testing.assert_array_equal(np.bincount(a.kind), np.bincount(c.kind))
    ra, rc = (tr.make_reads(np.random.default_rng(0), np.random.default_rng(s),
                            MIX, ds.n, 20.0) for s in (5, 6))
    assert ra.due.size == rc.due.size and ra.pinned.sum() == rc.pinned.sum()
    assert not np.array_equal(ra.rows, rc.rows)


def test_events_never_delete_a_missing_edge_or_insert_a_live_one(ds):
    ev = _events(ds, 11)
    live = set(range(ds.num_base))
    for k, e in zip(ev.kind, ev.edge):
        if k == tr.INSERT:
            assert e not in live and e >= ds.num_base
            live.add(e)
        elif k == tr.DELETE:
            assert e in live
            live.remove(e)
    assert (ev.vertex[ev.kind == tr.FEATURE] >= 0).all()


def test_poisson_rates_and_kind_shares(ds):
    gaps = tr.arrival_gaps(np.random.default_rng(0), 40.0, 500.0)
    # 20,000 expected; a Poisson count's sd is sqrt(20,000) ≈ 141
    assert abs(gaps.size - 20_000) < 5 * 141
    assert gaps.mean() == pytest.approx(1 / 40.0, rel=0.03)
    assert gaps.sum() < 500.0 and (gaps > 0).all()
    # exponential gaps: the sd equals the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)
    ev = _events(ds, 2, count=4000)
    share = np.bincount(ev.kind, minlength=3) / len(ev)
    np.testing.assert_allclose(share, [MIX["insert_share"], MIX["delete_share"],
                                       MIX["feature_share"]], atol=0.03)


def test_reads_ask_for_distinct_rows_with_a_zipf_head(ds):
    reads = tr.make_reads(np.random.default_rng(3), np.random.default_rng(4),
                          MIX, ds.n, 100.0)
    assert reads.due.size == pytest.approx(MIX["read_rate_per_s"] * 100, rel=0.1)
    assert reads.rows.shape[1] == MIX["read_rows"]
    for r in reads.rows:
        assert np.unique(r).size == r.size
    assert reads.rows.min() >= 0 and reads.rows.max() < ds.n
    assert reads.pinned.mean() == pytest.approx(MIX["pinned_share"], abs=0.04)
    counts = np.bincount(reads.rows.ravel(), minlength=ds.n)
    # the hottest rows are asked for far more often than the median row
    assert np.sort(counts)[-5:].min() > 4 * np.median(counts)


def test_batch_nets_out_edges_and_keeps_the_last_feature_row(ds):
    e_new = ds.num_base  # a pool edge: inserted, then deleted in one batch
    e_old = 3  # a base edge, deleted
    vals = np.arange(16, dtype=np.float32).reshape(2, 8)
    ev = tr.Events(
        due=np.zeros(5), kind=np.array([0, 1, 1, 2, 2], np.int8),
        edge=np.array([e_new, e_new, e_old, -1, -1]),
        vertex=np.array([-1, -1, -1, 9, 9]), feat=np.array([-1, -1, -1, 0, 1]),
        values=vals)
    b = tr.build_batch(ev, 0, 5, ds.pairs)
    assert b.ins_src.size == 0
    u, v = ds.pairs[e_old]
    assert sorted(zip(b.del_src, b.del_dst)) == sorted([(u, v), (v, u)])
    assert b.feat_vertices.tolist() == [9]
    np.testing.assert_array_equal(b.feat_values, vals[1:])


def test_warmup_is_fixed_and_can_leave_features_out(ds):
    mix = dict(MIX, warmup=[{"events": 50, "features": True},
                            {"events": 50, "features": False}])
    parts = tr.make_warmup(np.random.default_rng(1),
                           tr.LiveEdges(ds.num_base, ds.pairs.shape[0]), mix,
                           ds.n, 8)
    assert [len(p) for p in parts] == [50, 50]
    assert (parts[1].kind != tr.FEATURE).all()
    assert np.isnan(parts[0].due).all()


def test_exhausted_pool_raises():
    live = tr.LiveEdges(2, 3)
    live.insert()
    with pytest.raises(RuntimeError, match="exhausted"):
        live.insert()
