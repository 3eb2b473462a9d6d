"""Shared small utilities used across the repro framework."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def register_pytree_dataclass(cls):
    """Register a (frozen) dataclass as a JAX pytree.

    Fields annotated with ``static=True`` in their ``field(metadata=...)`` are
    treated as auxiliary (static) data; everything else is a child.
    """
    fields = dataclasses.fields(cls)
    data_names = [f.name for f in fields if not f.metadata.get("static", False)]
    meta_names = [f.name for f in fields if f.metadata.get("static", False)]

    def flatten(obj):
        return (
            tuple(getattr(obj, n) for n in data_names),
            tuple(getattr(obj, n) for n in meta_names),
        )

    def unflatten(meta, data):
        kwargs = dict(zip(data_names, data))
        kwargs.update(dict(zip(meta_names, meta)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def static_field(**kwargs):
    """Dataclass field held as static pytree aux data."""
    return dataclasses.field(metadata={"static": True}, **kwargs)


def pairwise_sq_dists(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared Euclidean distance matrix between rows of x (M,d) and y (N,d).

    Uses the MXU-friendly ||x||^2 + ||y||^2 - 2 x.y^T formulation with a
    clamp at zero to guard against negative round-off. The product runs at
    full f32 precision on every backend (a TPU's default would be one bf16
    pass).
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (M, 1)
    y2 = jnp.sum(y * y, axis=-1, keepdims=True).T  # (1, N)
    d = x2 + y2 - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d, 0.0)


def topk_smallest(values: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Indices and values of the k smallest entries along the last axis."""
    neg_vals, idx = jax.lax.top_k(-values, k)
    return -neg_vals, idx


def recall_at_k(result_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """Mean recall@k over queries: |R ∩ R*| / k."""
    r = 0.0
    for res, gt in zip(result_ids, gt_ids):
        r += len(set(res[:k].tolist()) & set(gt[:k].tolist())) / k
    return r / len(result_ids)


def mean_relative_error(
    result_dists: np.ndarray, gt_dists: np.ndarray
) -> float:
    """Paper MRE: (1/k) sum (||q,o_i|| - ||q,o_i*||) / ||q,o_i*||, averaged over queries."""
    rd = np.sqrt(np.maximum(np.asarray(result_dists, dtype=np.float64), 0.0))
    gd = np.sqrt(np.maximum(np.asarray(gt_dists, dtype=np.float64), 0.0))
    denom = np.maximum(gd, 1e-12)
    return float(np.mean((rd - gd) / denom))


def exact_knn(data: jax.Array, queries: jax.Array, k: int, batch: int = 256):
    """Brute-force exact k-NN ground truth (squared distances)."""

    @jax.jit
    def _one(qb, db):
        d = pairwise_sq_dists(qb, db)
        return topk_smallest(d, k)

    data = jnp.asarray(data)
    dists, ids = [], []
    for i in range(0, queries.shape[0], batch):
        dv, iv = _one(queries[i : i + batch], data)
        dists.append(np.asarray(dv))
        ids.append(np.asarray(iv))
    return np.concatenate(dists), np.concatenate(ids)


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(
        sum(l.size * l.dtype.itemsize for l in leaves if hasattr(l, "dtype"))
    )
