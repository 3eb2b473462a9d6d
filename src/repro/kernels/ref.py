"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function is the mathematical spec; kernel tests sweep shapes/dtypes and
assert allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def l2dist_ref(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared Euclidean distances between rows of x (M, d) and y (N, d)."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    y2 = jnp.sum(y * y, axis=-1, keepdims=True).T
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)


def kmeans_assign_ref(x: jax.Array, c: jax.Array):
    """(assignments (n,) int32, min squared distance (n,) f32)."""
    d = l2dist_ref(x, c)
    return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)


def scscore_ref(d1s, d2s, a1s, a2s, taus):
    """SC-scores (Q, n) int32.

    d1s/d2s: (N_s, Q, sqrt_k) query-to-centroid distances;
    a1s/a2s: (N_s, n) int32 cell assignments; taus: (N_s, Q) thresholds.
    SC[q, p] = #subspaces s with d1s[s,q,a1s[s,p]] + d2s[s,q,a2s[s,p]] <= taus[s,q].
    """
    n_sub = d1s.shape[0]
    sc = jnp.zeros((d1s.shape[1], a1s.shape[1]), jnp.int32)
    for s in range(n_sub):
        sums = jnp.take(d1s[s], a1s[s], axis=1) + jnp.take(d2s[s], a2s[s], axis=1)
        sc = sc + (sums <= taus[s][:, None]).astype(jnp.int32)
    return sc


def schist_ref(d1s, d2s, a1s, a2s, taus, n_levels: int):
    """Per-query SC-score histogram (Q, n_levels) int32 — materializing
    spec for the streaming schist kernel: hist[q, l] = #points with
    SC[q, p] == l, over ALL n points (level 0 included)."""
    sc = scscore_ref(d1s, d2s, a1s, a2s, taus)
    return jnp.stack(
        [jnp.sum(sc == l, axis=1) for l in range(n_levels)], axis=1
    ).astype(jnp.int32)


def masked_rerank_ref(d1s, d2s, a1s, a2s, taus, thresh, queries, data,
                      data_norms, k: int):
    """Masked full re-rank spec: exact distances of every point with
    SC >= thresh, top-k smallest (distance-major, id-minor; id -1 / +inf
    where fewer than k points pass). Materializes the (Q, n) matrices the
    streaming kernel avoids."""
    sc = scscore_ref(d1s, d2s, a1s, a2s, taus)
    q = queries.astype(jnp.float32)
    x = data.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    qx = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    dist = jnp.maximum(qn - 2.0 * qx + data_norms[None, :], 0.0)
    dist = jnp.where(sc >= thresh[:, None], dist, jnp.inf)
    neg, ids = jax.lax.top_k(-dist, k)  # stable: ties -> lowest id
    top_d = -neg
    ids = jnp.where(jnp.isfinite(top_d), ids, -1)
    vecs = jnp.take(data, jnp.maximum(ids, 0), axis=0)
    diff = vecs - queries[:, None, :]
    exact = jnp.where(ids >= 0, jnp.sum(diff * diff, axis=-1), jnp.inf)
    return ids.astype(jnp.int32), exact


def flash_attention_ref(q, k, v, causal: bool = True):
    """Softmax attention oracle. q (BH,S,hd), k/v (BH,T,hd)."""
    s = jnp.einsum(
        "bsd,btd->bst", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (q.shape[-1] ** -0.5)
    if causal:
        mask = jnp.arange(k.shape[1])[None, :] <= jnp.arange(q.shape[1])[:, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32)).astype(q.dtype)
