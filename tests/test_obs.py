"""Spans and the compile tally (``repro.obs``) on the served path: what
``BatchStats`` and ``ServingFrontend.read_rounds`` report is what the spans
measured, compiles land in the span that caused them, and the fused step's
HLO names each layer and stage."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import make_model
from repro.core.incremental import fused_stream_step
from repro.graph import make_graph, make_stream
from repro.graph.generators import random_features
from repro.serve import EngineConfig, ServingFrontend, create_engine

STAGES = ("messages", "scatter", "delta_agg", "constrained", "update")


def _engine(model_name="gcn", num_batches=4, seed=0, n=120):
    model = make_model(model_name)
    g = make_graph("powerlaw", n, avg_degree=5, seed=seed, weighted=True)
    x, _ = random_features(n, 8, seed=seed)
    wl = make_stream(g, num_batches=num_batches, batch_edges=8,
                     delete_frac=0.35, seed=seed + 1, feature_dim=8,
                     feature_frac=0.05)
    params = model.init_layers(jax.random.PRNGKey(0), [8, 8, 8])
    eng = create_engine("device", EngineConfig(model=model, graph=wl.base,
                                               x=x, params=params))
    return eng, wl


@pytest.fixture
def recorded(monkeypatch):
    """Every span the program opens, in closing order."""
    closed = []
    real = obs.span

    @contextlib.contextmanager
    def span(name):
        with real(name) as s:
            yield s
        closed.append(s)

    monkeypatch.setattr(obs, "span", span)
    return closed


def test_spans_nest_and_hand_up_time_compiles_and_counts():
    with obs.span("t_outer") as outer:
        with obs.span("t_mid") as mid:
            with obs.span("t_leaf") as leaf:
                obs.count("t_bytes", 5)
            obs.count("t_bytes", 2)
    assert outer.seconds >= mid.seconds >= leaf.seconds > 0.0
    assert mid.inner("t_leaf") == leaf.seconds
    assert outer.inner("t_mid") == mid.seconds
    assert outer.inner("t_leaf") == leaf.seconds
    assert (leaf.counts, mid.counts, outer.counts) == (
        {"t_bytes": 5}, {"t_bytes": 7}, {"t_bytes": 7})
    assert outer.start <= mid.start <= leaf.start
    obs.count("t_bytes", 1)  # no span open: dropped, never raises


def test_fresh_compile_is_charged_to_the_innermost_span():
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    fresh = jax.jit(lambda v: v * 3.25 - 0.5)  # a new function: compiles
    before = {k: list(v) for k, v in obs.TALLY.by_span.items()}
    total = obs.TALLY.compiles
    with obs.span("t_parent") as parent:
        with obs.span("t_child") as child:
            jax.block_until_ready(fresh(x))
    assert child.compiles >= 1 and child.compile_s > 0.0
    assert parent.compiles == child.compiles  # handed up, not charged twice
    assert obs.TALLY.compiles - total == child.compiles
    got = obs.TALLY.by_span["t_child"][0] - before.get("t_child", [0])[0]
    assert got == child.compiles
    assert obs.TALLY.by_span.get("t_parent", [0])[0] == \
        before.get("t_parent", [0])[0]
    # a second call hits jit's cache: nothing new anywhere
    with obs.span("t_child") as again:
        jax.block_until_ready(fresh(x))
    assert again.compiles == 0


def test_batch_stats_are_the_spans_readings(recorded):
    eng, wl = _engine()
    fe = ServingFrontend(eng, max_pending_reads=64, max_versions=4)
    backend = eng._orch.backend
    preps = []
    dispatch = backend.dispatch
    backend.dispatch = lambda prep: (preps.append(prep), dispatch(prep))[1]
    rows = np.arange(0, 120, 3)
    stats, served = [], []
    for i, b in enumerate(wl.batches):
        if i % 2:  # reads before every other batch: served by apply_batch
            fe.submit_read(rows)
            fe.submit_read(rows[::2], version=max(fe.version - 1, 0))
        del recorded[:]
        stats.append(fe.apply_batch(b))
        served.append(i % 2 == 1)
        spans = {s.name: s for s in recorded}
        bs = stats[-1]
        assert bs.graph_time_s == spans["graph"].seconds
        assert bs.plan_time_s == spans["plan"].seconds
        assert bs.exec_time_s == spans["exec"].seconds
        assert bs.pack_time_s == spans["plan/pack"].seconds
        assert bs.hook_time_s == spans["undo_capture"].seconds
        assert 0.0 < bs.pack_time_s <= bs.plan_time_s
        assert 0.0 < bs.hook_time_s <= bs.exec_time_s
        assert spans["plan/build"].seconds + bs.pack_time_s <= bs.plan_time_s
        assert (spans["device_put"].seconds + spans["step"].seconds
                + spans["sync"].seconds + bs.hook_time_s) <= bs.exec_time_s
        assert bs.compiles == sum(spans[k].compiles
                                  for k in ("graph", "plan", "exec"))
        p = preps[-1]
        want = (p.idx.nbytes + p.flt.nbytes + p.msk.nbytes
                + (0 if p.feat_vals is None else p.feat_vals.nbytes))
        assert bs.h2d_bytes == want > 0
    assert stats[0].compiles > 0  # the first batch traces the fused step
    # one read round per round that served (two pinned groups each)
    assert len(fe.read_rounds) == sum(served)
    for r in fe.read_rounds:
        assert (r.reads, r.groups) == (2, 2)
        assert r.union_rows == rows.size + rows[::2].size
        assert 0.0 < r.gather_s + r.undo_s <= r.seconds
        assert r.compile_s >= 0.0 and r.compiles >= 0
    assert fe.serve_reads() == 0 and len(fe.read_rounds) == sum(served)


@pytest.mark.parametrize("model_name", ["gcn", "gat"])
def test_fused_step_hlo_names_every_layer_and_stage(model_name):
    eng, wl = _engine(model_name, num_batches=1)
    backend = eng._orch.backend
    g_new = eng._orch._apply_graph(wl.batches[0])
    packed = backend.plan(eng._orch.graph, g_new, wl.batches[0])
    args = (backend.model, packed.layout, tuple(backend.params),
            tuple(backend._h), tuple(backend._a), tuple(backend._nct),
            packed.idx, packed.flt, packed.msk, packed.feat_vals,
            packed.pallas)
    text = fused_stream_step.lower(*args).as_text(debug_info=True)
    stages = STAGES if backend.model.dest_dependent else (
        tuple(s for s in STAGES if s != "constrained"))
    for l in range(backend.L):
        for stage in stages:
            assert f"layer{l}/{stage}" in text, (l, stage)
    assert f"layer{backend.L}/" not in text
