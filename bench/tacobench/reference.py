"""The plain reference: exact k nearest neighbours under squared L2.

A blockwise brute force on the device, independent of the code under test
(it imports nothing of ``repro``). For each block of queries it streams the
corpus in row chunks, computes every squared distance in float32 at
``Precision.HIGHEST`` as ``|q|^2 - 2 q.x + |x|^2``, and keeps the best
``k + margin`` of each chunk. The survivors are then scored again as
``sum((x - q)^2)`` in float32, which has no cancellation, and sorted by
(distance, id). The margin keeps a true neighbour that the cancelling form
misorders by rounding among the survivors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
MARGIN = 8


def _chunk(n: int, target: int = 1 << 18) -> int:
    """Largest divisor of ``n`` that is at most ``target``."""
    best = 1
    for c in range(1, int(np.sqrt(n)) + 1):
        if n % c == 0:
            for rows in (c, n // c):
                if best < rows <= target:
                    best = rows
    return best


@functools.partial(jax.jit, static_argnames=("keep", "rows"))
def _block_candidates(corpus, q, *, keep: int, rows: int):
    """ids (Qb, keep) of the ``keep`` smallest ``|q|^2 - 2 q.x + |x|^2``."""
    n, d = corpus.shape
    qn = jnp.sum(q * q, axis=1)

    def body(c, best):
        best_d, best_i = best
        x = jax.lax.dynamic_slice(corpus, (c * rows, 0), (rows, d))
        dist = (qn[:, None] - 2.0 * jnp.matmul(q, x.T, precision=_HI)
                + jnp.sum(x * x, axis=1)[None, :])
        neg, idx = jax.lax.top_k(-dist, keep)
        cand_d = jnp.concatenate([best_d, -neg], axis=1)
        cand_i = jnp.concatenate([best_i, idx + c * rows], axis=1)
        neg, pos = jax.lax.top_k(-cand_d, keep)
        return -neg, jnp.take_along_axis(cand_i, pos, axis=1)

    init = (jnp.full((q.shape[0], keep), jnp.inf, jnp.float32),
            jnp.zeros((q.shape[0], keep), jnp.int32))
    return jax.lax.fori_loop(0, n // rows, body, init)[1]


@jax.jit
def exact_sq_dists(corpus, queries, ids):
    """``sum((corpus[ids] - q)^2)`` in float32, (Q, m); ``ids`` are in range."""
    diff = jnp.take(corpus, ids, axis=0) - queries[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def _refine(corpus, q, cand, *, k: int):
    dist = exact_sq_dists(corpus, q, cand)
    # sort by (distance, id): ids first, then a stable sort by distance
    o1 = jnp.argsort(cand, axis=1, stable=True)
    cand = jnp.take_along_axis(cand, o1, axis=1)
    dist = jnp.take_along_axis(dist, o1, axis=1)
    o2 = jnp.argsort(dist, axis=1, stable=True)[:, :k]
    return jnp.take_along_axis(cand, o2, axis=1), jnp.take_along_axis(
        dist, o2, axis=1)


def exact_knn(corpus, queries, k: int, *, query_block: int = 256):
    """``(ids (Q, k) int64, sq_dists (Q, k) float32)`` as numpy arrays, for
    ``queries`` (numpy or device, (Q, d)) against the device ``corpus``."""
    queries = np.asarray(queries, np.float32)
    n = corpus.shape[0]
    keep = min(n, k + MARGIN)
    rows = _chunk(n)
    qb = min(query_block, max(1, len(queries)))
    out_i, out_d = [], []
    for lo in range(0, len(queries), qb):
        block = queries[lo:lo + qb]
        m = len(block)
        if m < qb:  # one shape per run: pad the last block with its last row
            block = np.concatenate([block, np.repeat(block[-1:], qb - m, 0)])
        q = jnp.asarray(block)
        cand = _block_candidates(corpus, q, keep=keep, rows=rows)
        ids, dists = _refine(corpus, q, cand, k=k)
        out_i.append(np.asarray(ids)[:m])
        out_d.append(np.asarray(dists)[:m])
    return (np.concatenate(out_i).astype(np.int64),
            np.concatenate(out_d).astype(np.float32))
