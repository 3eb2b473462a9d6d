"""Fused K-means assignment Pallas kernel: distance + argmin, no (n, k)
matrix in HBM.

Grid over point blocks; the full centroid set (k <= ~1024, small d) stays
VMEM-resident across the grid. Each step computes the (bn, k) distance tile
and reduces it to (argmin, min) immediately — the classic memory-bound
fusion for Lloyd iterations.

Inputs pre-padded: points to bn multiples, centroid count to 128 multiples
(padding centroids have huge coordinates so they never win the argmin),
feature dim to 8 multiples (zero-pad, exact for L2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, a_ref, d_ref):
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    c = c_ref[...].astype(jnp.float32)  # (k, d)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1, keepdims=True).T
    prod = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    dist = jnp.maximum(x2 + c2 - 2.0 * prod, 0.0)  # (bn, k)
    a_ref[...] = jnp.argmin(dist, axis=1).astype(jnp.int32)
    d_ref[...] = jnp.min(dist, axis=1)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_assign_pallas(
    x: jax.Array, c: jax.Array, *, bn: int = 256, interpret: bool = False
):
    """x (n, d) pre-padded to bn multiples; c (k, d) with k a lane multiple."""
    n, d = x.shape
    k, d2 = c.shape
    assert d == d2 and n % bn == 0, (x.shape, c.shape)
    grid = (n // bn,)
    return pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
