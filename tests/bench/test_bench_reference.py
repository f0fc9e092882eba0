"""The plain reference against the program's own full recompute, its
independence from the program, and the cone that ``step_mfu`` counts."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import reference  # noqa: E402
from chipbench.graphs import load_dataset  # noqa: E402
from chipbench.harness import load_module  # noqa: E402

GRAPH = {"generator": "barabasi_albert", "n": 300, "m": 3, "seed": 1,
         "pool_edges": 40}
DIMS = [8, 16, 16, 4]
FAMILIES = ["gcn", "sage"]


def _model(family):
    return load_module(ROOT / "chipbench" / "models" / f"{family}.py")


@pytest.fixture(scope="module")
def ds():
    return load_dataset(GRAPH, None)


def _ref(model, params, x, ds, alive_u, dot="highest", block=64):
    src, dst = ds.directed()
    s, d, blk = reference.pad_edges(src, dst, block)
    alive = np.zeros(s.size, np.float32)
    alive[:src.size] = np.concatenate([alive_u, alive_u])
    return np.asarray(reference.forward(model.layer, dot, blk, params,
                                        jnp.asarray(x), jnp.asarray(s),
                                        jnp.asarray(d), jnp.asarray(alive)))


@pytest.mark.parametrize("family", FAMILIES)
def test_reference_matches_the_programs_full_forward(ds, family):
    from repro.core.full import full_forward
    from repro.core.models import make_model
    from repro.graph.csr import CSRGraph

    model = _model(family)
    params = model.init_params(jax.random.PRNGKey(0), DIMS)
    x = np.random.default_rng(0).standard_normal((ds.n, DIMS[0])).astype(np.float32)
    alive_u = np.r_[np.ones(ds.num_base, bool), np.zeros(ds.num_pool, bool)]
    alive_u[::7] = False  # some base edges deleted, so degrees vary
    p = ds.pairs[alive_u].astype(np.int64)
    g = CSRGraph.from_edges(ds.n, np.r_[p[:, 0], p[:, 1]], np.r_[p[:, 1], p[:, 0]])
    want = np.asarray(full_forward(make_model(model.ENGINE_MODEL), params,
                                   jnp.asarray(x), g)[-1].h)
    got = _ref(model, params, x, ds, alive_u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_blocked_aggregation_equals_one_block(ds):
    model = _model("sage")
    params = model.init_params(jax.random.PRNGKey(1), DIMS)
    x = np.random.default_rng(1).standard_normal((ds.n, DIMS[0])).astype(np.float32)
    alive_u = np.ones(ds.pairs.shape[0], bool)
    a = _ref(model, params, x, ds, alive_u, block=64)
    b = _ref(model, params, x, ds, alive_u, block=1 << 20)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_three_pass_product_sits_between_bf16_and_float32():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((64, 256)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((256, 64)).astype(np.float32))
    exact = np.asarray(a, np.float64) @ np.asarray(w, np.float64)
    three = np.abs(np.asarray(reference.dot_three_pass(a, w)) - exact).max()
    one = np.abs(np.asarray(jnp.dot(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                                    preferred_element_type=jnp.float32)) - exact).max()
    full = np.abs(np.asarray(reference.dot_highest(a, w)) - exact).max()
    assert full < three < one / 30


def test_reference_and_models_import_nothing_of_the_program():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from chipbench import reference, graphs, traffic, trace_reduce\n"
        "from chipbench.harness import load_module\n"
        "for f in ('gcn', 'sage'):\n"
        "    load_module(__import__('pathlib').Path(%r) / (f + '.py'))\n"
        "bad = [m for m in sys.modules if m == 'repro' or m.startswith('repro.')]\n"
        "assert not bad, bad\n"
        "print('clean')\n" % (str(ROOT), str(ROOT / "chipbench" / "models")))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr


@pytest.mark.parametrize("family", FAMILIES)
def test_cone_holds_every_row_the_batch_changes(ds, family):
    """Every row whose reference output moves after a batch lies in the
    model file's cone at the last layer (the cone may hold more)."""
    from chipbench import traffic as tr

    model = _model(family)
    params = model.init_params(jax.random.PRNGKey(3), DIMS)
    rng = np.random.default_rng(3)
    x0 = rng.standard_normal((ds.n, DIMS[0])).astype(np.float32)
    live = tr.LiveEdges(ds.num_base, ds.pairs.shape[0])
    ev = tr.make_events(rng, live, tr.draw_kinds(rng, 12, (0.5, 0.3, 0.2)), ds.n,
                        DIMS[0])
    before = np.r_[np.ones(ds.num_base, bool), np.zeros(ds.num_pool, bool)]
    after = before.copy()
    x1 = x0.copy()
    for k, e, v, f in zip(ev.kind, ev.edge, ev.vertex, ev.feat):
        if k == tr.INSERT:
            after[e] = True
        elif k == tr.DELETE:
            after[e] = False
        else:
            x1[v] = ev.values[f]
    h0 = _ref(model, params, x0, ds, before)
    h1 = _ref(model, params, x1, ds, after)
    moved = np.abs(h1 - h0).max(axis=1) > 0
    src, dst = ds.directed()
    touched = np.zeros(ds.n, bool)
    touched[ds.pairs[before != after].ravel()] = True
    deg0 = np.bincount(dst[np.r_[before, before]], minlength=ds.n)
    deg1 = np.bincount(dst[np.r_[after, after]], minlength=ds.n)
    masks = model.cone(src, dst, np.r_[after, after], touched, deg0 != deg1,
                       ev.vertex[ev.kind == tr.FEATURE], ds.n, len(DIMS) - 1)
    assert moved.any()
    assert not (moved & ~masks[-1]).any()
