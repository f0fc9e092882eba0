"""The plain reference: each family's layer equations over the benchmark's
own replay of the event log.  Nothing here imports the program.

The graph is the master directed edge list (base and pool edges, both
directions) with a 0/1 ``alive`` weight per edge, so a version is a mask and
no adjacency is ever rebuilt.  Messages are summed in fixed blocks of edges,
so the reference's memory stays at one block of rows whatever the graph.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EDGE_BLOCK = 1 << 18


class Graph(NamedTuple):
    src: jax.Array  # [E_pad] int32 (padding: 0, with alive 0)
    dst: jax.Array  # [E_pad] int32
    alive: jax.Array  # [E_pad] float32, 1 where the edge is live
    deg: jax.Array  # [n] float32 in-degree over live edges


def pad_edges(src: np.ndarray, dst: np.ndarray, block: int = EDGE_BLOCK):
    e = src.shape[0]
    blk = min(block, max(1, e))
    e_pad = -(-e // blk) * blk
    s = np.zeros(e_pad, np.int32)
    d = np.zeros(e_pad, np.int32)
    s[:e], d[:e] = src, dst
    return s, d, blk


def aggregate(g: Graph, h: jax.Array, coef: jax.Array, block: int) -> jax.Array:
    """``out[v] = Σ_e coef[e] · h[src[e]]`` over edges with ``dst[e] = v``,
    summed one block of edges at a time."""
    nb = g.src.shape[0] // block
    src = g.src.reshape(nb, block)
    dst = g.dst.reshape(nb, block)
    cf = coef.reshape(nb, block)

    def body(i, acc):
        msg = h[src[i]] * cf[i][:, None]
        return acc.at[dst[i]].add(msg)

    return jax.lax.fori_loop(0, nb, body,
                             jnp.zeros((h.shape[0], h.shape[1]), h.dtype))


def dot_highest(a: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)


def _split_bf16(x: jax.Array):
    """x = hi + lo + (rest), hi and lo each rounded to bfloat16 precision.
    ``reduce_precision`` keeps float32 storage, which the compiler may not
    fold away as it may a float32→bfloat16→float32 round trip."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def dot_three_pass(a: jax.Array, w: jax.Array) -> jax.Array:
    """float32 product in three bfloat16 passes (hi·hi + hi·lo + lo·hi)
    with float32 sums, the precision one step below "highest": the
    control's arithmetic.  The products of bfloat16-exact factors are exact
    at "highest", so this is the same on every backend."""
    ah, al = _split_bf16(a)
    wh, wl = _split_bf16(w)
    return dot_highest(ah, wh) + (dot_highest(ah, wl) + dot_highest(al, wh))


DOTS = {"highest": dot_highest, "three_pass": dot_three_pass}


@partial(jax.jit, static_argnames=("layer", "dot", "block"))
def forward(layer: Callable, dot: str, block: int, params, x, src, dst,
            alive) -> jax.Array:
    """Final-layer embeddings ``[n, d_L]`` of the live graph."""
    n = x.shape[0]
    deg = jnp.zeros(n, jnp.float32).at[dst].add(alive)
    g = Graph(src, dst, alive, deg)
    agg = partial(aggregate, g, block=block)
    h = x
    for p in params:
        h = layer(p, h, g, agg, DOTS[dot])
    return h


def relative_rms(got: np.ndarray, ref: np.ndarray) -> float:
    """sqrt(mean((got − ref)²)) / sqrt(mean(ref²)): a gap over every entry,
    steady from seed to seed where a largest gap swings."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    d = got.astype(np.float64) - ref
    den = float(np.sqrt(np.mean(np.square(ref, dtype=np.float64))))
    return float(np.sqrt(np.mean(d * d))) / max(den, 1e-30)


def scaled_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest absolute difference, relative to max(1, max |ref|)."""
    if got.shape != ref.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    scale = max(1.0, float(np.abs(ref).max()) if ref.size else 1.0)
    return float(np.abs(got.astype(np.float64) - ref).max() / scale) if ref.size else 0.0
