"""Subspace-oriented data transformation via entropy averaging (paper Alg. 1 + 2).

The transformation computes the sample covariance of the corpus, keeps the top
``N_s * s`` eigenvectors, and allocates them to ``N_s`` buckets of ``s``
eigenvectors each so that the running *product of eigenvalues* (= exp of the
subspace differential entropy up to constants) is balanced across buckets
(Theorem 1: this greedy allocation solves the min-max entropy-averaging
problem (4) of the paper).

Numerical notes vs. the paper's pseudocode:
  * Algorithm 2 line 3 rescales eigenvalues so all are >= 1 and tracks raw
    products. We track *log* products instead (and shift logs so the smallest
    retained one is 0), which is exactly equivalent for the argmin and does
    not overflow for large d.
  * The allocation itself is a tiny O(N_s * s) sequential greedy; it runs on
    host (numpy) at build time. The transformation (mean-center + matmul with
    the allocated basis) is pure JAX and jit/pjit friendly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import register_pytree_dataclass, static_field


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class SubspaceTransform:
    """Fitted transformation. ``basis`` columns are grouped by subspace:
    columns [j*s, (j+1)*s) form B_j."""

    mean: jax.Array  # (d,)
    basis: jax.Array  # (d, n_subspaces * s)
    eigvals: jax.Array  # (n_subspaces * s,) eigenvalues in allocation order
    n_subspaces: int = static_field()
    subspace_dim: int = static_field()

    @property
    def out_dim(self) -> int:
        return self.n_subspaces * self.subspace_dim

    def __call__(self, x: jax.Array) -> jax.Array:
        return apply_transform(self, x)


def eigensystem_allocation(
    eigvals: np.ndarray, n_subspaces: int, subspace_dim: int
) -> list[list[int]]:
    """Paper Algorithm 2. Returns, per subspace, the indices (into the
    descending-sorted eigen list) of the eigenvectors allocated to it.

    Greedy: walk the top ``n_subspaces * subspace_dim`` eigenvalues in
    descending order; assign each to the not-yet-full bucket with the
    smallest running (log-)product.
    """
    m = n_subspaces * subspace_dim
    if m > len(eigvals):
        raise ValueError(
            f"n_subspaces*subspace_dim={m} exceeds data dimensionality {len(eigvals)}"
        )
    order = np.argsort(eigvals)[::-1][:m]
    lam = np.asarray(eigvals, dtype=np.float64)[order]
    # Alg.2 line 3: scale so all eigenvalues >= 1 (log >= 0). In log space this
    # is a constant shift per item; use max(smallest, tiny) to guard zeros.
    lam = np.maximum(lam, 1e-30)
    log_lam = np.log(lam)
    log_lam = log_lam - min(log_lam[-1], 0.0)  # shift so every log >= 0

    buckets: list[list[int]] = [[] for _ in range(n_subspaces)]
    log_prod = np.zeros(n_subspaces, dtype=np.float64)
    for i in range(m):
        avail = [j for j in range(n_subspaces) if len(buckets[j]) < subspace_dim]
        j = min(avail, key=lambda b: (log_prod[b], b))
        buckets[j].append(int(order[i]))
        log_prod[j] += log_lam[i]
    return buckets


def fit_transform(
    data: jax.Array, n_subspaces: int, subspace_dim: int
) -> SubspaceTransform:
    """Paper Algorithm 1 lines 2-5: mean, covariance, eigendecomposition,
    eigensystem allocation. Returns the fitted transform (not the transformed
    data; see :func:`apply_transform`)."""
    mean, eigvals, eigvecs = _cov_eig(jnp.asarray(data, dtype=jnp.float32))
    return allocate_from_eig(
        mean, np.asarray(eigvals), np.asarray(eigvecs), n_subspaces, subspace_dim
    )


@jax.jit
def _cov_eig(data: jax.Array):
    n = data.shape[0]
    mean = jnp.mean(data, axis=0)
    centered = data - mean
    cov = jnp.matmul(
        centered.T, centered, precision=jax.lax.Precision.HIGHEST
    ) / jnp.maximum(n - 1, 1)
    eigvals, eigvecs = jnp.linalg.eigh(cov)  # ascending
    return mean, eigvals, eigvecs


def allocate_from_eig(
    mean: jax.Array,
    eigvals: np.ndarray,
    eigvecs: np.ndarray,
    n_subspaces: int,
    subspace_dim: int,
) -> SubspaceTransform:
    """Build the transform from a precomputed eigensystem (used by both the
    single-host and the distributed builder)."""
    buckets = eigensystem_allocation(eigvals, n_subspaces, subspace_dim)
    cols, vals = [], []
    for bucket in buckets:
        for idx in bucket:
            cols.append(np.asarray(eigvecs)[:, idx])
            vals.append(float(np.asarray(eigvals)[idx]))
    basis = jnp.asarray(np.stack(cols, axis=1), dtype=jnp.float32)
    return SubspaceTransform(
        mean=jnp.asarray(mean, dtype=jnp.float32),
        basis=basis,
        eigvals=jnp.asarray(vals, dtype=jnp.float32),
        n_subspaces=n_subspaces,
        subspace_dim=subspace_dim,
    )


def apply_transform(t: SubspaceTransform, x: jax.Array) -> jax.Array:
    """Paper Algorithm 1 lines 6-11 (vectorized): (x - mean) @ B.

    Output columns are grouped per subspace; column block j is B_j^T(x-mean).
    """
    return jnp.matmul(
        jnp.asarray(x, dtype=jnp.float32) - t.mean, t.basis,
        precision=jax.lax.Precision.HIGHEST,
    )


def identity_transform(d: int, dim_order: np.ndarray | None = None):
    """A 'transform' that just (optionally) permutes raw dimensions — used by
    the SuCo baseline (Def. 4 subspace sampling, data-agnostic)."""
    if dim_order is None:
        dim_order = np.arange(d)
    basis = np.zeros((d, len(dim_order)), dtype=np.float32)
    basis[np.asarray(dim_order), np.arange(len(dim_order))] = 1.0
    return jnp.zeros((d,), jnp.float32), jnp.asarray(basis)
