"""GraphSAGE with mean aggregation, in the program's convention
(``repro.core.models.GraphSAGE``):

    h_v' = relu( h_v · W_self + mean_{u→v} h_u · W_nbr + b )

with the mean taken as 0 where v has no live in-edge.  Weights and biases
are drawn from the run's seed as in ``gcn.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ENGINE_MODEL = "sage"
MATRICES_PER_LAYER = 2


def init_params(key, dims):
    out = []
    for k, (d_in, d_out) in zip(jax.random.split(key, len(dims) - 1),
                                zip(dims[:-1], dims[1:])):
        k1, k2, kb = jax.random.split(k, 3)
        s = jnp.sqrt(2.0 / (d_in + d_out))
        out.append({
            "W_self": jax.random.normal(k1, (d_in, d_out), jnp.float32) * s,
            "W_nbr": jax.random.normal(k2, (d_in, d_out), jnp.float32) * s,
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32),
        })
    return out


def layer(p, h, g, agg, dot):
    s = agg(h, g.alive)
    live = g.deg > 0.5
    a = jnp.where(live[:, None], s / jnp.where(live, g.deg, 1.0)[:, None], 0.0)
    return jax.nn.relu(dot(h, p["W_self"]) + dot(a, p["W_nbr"]) + p["b"])


def cone(src, dst, alive_new, touched, deg_changed, feat_rows, n, num_layers):
    """Rows whose layer-l output changes, l = 1..L: a row one of whose
    in-edges was inserted or deleted (``touched``), a row whose own
    previous-layer output changed, and a live out-neighbour of such a row.
    A source's degree is in no message, so ``deg_changed`` adds nothing."""
    changed = np.zeros(n, bool)
    changed[feat_rows] = True
    out = []
    for _ in range(num_layers):
        hit = alive_new & changed[src]
        nxt = touched | changed
        nxt[dst[hit]] = True
        out.append(nxt)
        changed = nxt
    return out
