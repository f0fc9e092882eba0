"""GCN, in the program's convention (``repro.core.models.GCN``):

    h_v' = relu( (Σ_{u→v} h_u / sqrt(d_u + 1)) / sqrt(d_v + 1) · W + b )

with d the in-degree over live edges (the self-loop count d̃ = d + 1 sits
only in the normalisation).  Weights and biases are drawn from the run's
seed: a normal Glorot draw for W and a small normal bias, standing for
BatchNorm folded into W and b at inference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ENGINE_MODEL = "gcn"
MATRICES_PER_LAYER = 1


def init_params(key, dims):
    out = []
    for k, (d_in, d_out) in zip(jax.random.split(key, len(dims) - 1),
                                zip(dims[:-1], dims[1:])):
        kw, kb = jax.random.split(k)
        out.append({
            "W": jax.random.normal(kw, (d_in, d_out), jnp.float32)
            * jnp.sqrt(2.0 / (d_in + d_out)),
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32),
        })
    return out


def layer(p, h, g, agg, dot):
    coef = g.alive * jax.lax.rsqrt(g.deg[g.src] + 1.0)
    s = agg(h, coef)
    a = s * jax.lax.rsqrt(g.deg + 1.0)[:, None]
    return jax.nn.relu(dot(a, p["W"]) + p["b"])


def cone(src, dst, alive_new, touched, deg_changed, feat_rows, n, num_layers):
    """Rows whose layer-l output changes, l = 1..L.  A row changes when one
    of its in-edges was inserted or deleted (``touched``), when its degree
    changed (its normalisation), or when a live in-neighbour's
    previous-layer output or degree changed: a source's degree sits in
    every message it sends."""
    changed = np.zeros(n, bool)
    changed[feat_rows] = True
    out = []
    for _ in range(num_layers):
        send = changed | deg_changed
        hit = alive_new & send[src]
        nxt = touched | deg_changed
        nxt[dst[hit]] = True
        out.append(nxt)
        changed = nxt
    return out
