"""Blocked squared-L2 distance matrix Pallas kernel.

Computes D[i, j] = ||x_i - y_j||^2 for X (M, d), Y (N, d) with explicit VMEM
tiling: grid (M/bm, N/bn, d/bk); each step accumulates the partial
x2 + y2 - 2 x.y^T contribution of one bk-wide dimension slab into the output
block, so the full (M, N) tile never leaves VMEM until done and the MXU sees
(bm, bk) @ (bk, bn) matmuls with 128-aligned shapes.

Inputs must be pre-padded to block multiples (the ops.py wrapper does this;
zero-padding the feature dim is exact for squared distances).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _l2dist_kernel(x_ref, y_ref, o_ref, *, n_k: int):
    k = pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    y = y_ref[...].astype(jnp.float32)  # (bn, bk)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)  # (bm, 1)
    y2 = jnp.sum(y * y, axis=1, keepdims=True).T  # (1, bn)
    prod = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    partial = x2 + y2 - 2.0 * prod

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += partial

    @pl.when(k == n_k - 1)
    def _clamp():
        o_ref[...] = jnp.maximum(o_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def l2dist_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x (M, d), y (N, d) pre-padded to multiples of (bm|bn, bk)."""
    m, d = x.shape
    n, d2 = y.shape
    assert d == d2 and m % bm == 0 and n % bn == 0 and d % bk == 0, (x.shape, y.shape)
    n_k = d // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_l2dist_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, y)
