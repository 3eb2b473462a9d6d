"""Fused SC-score collision-counting Pallas kernel (the query hot loop).

For a block of points and a block of queries, accumulates over all N_s
subspaces: SC[q, p] += (d1[s][q, a1[s][p]] + d2[s][q, a2[s][p]] <= tau[s][q]).

TPU adaptation (DESIGN.md §2): the per-point centroid-distance gather is
realized as a one-hot matmul — onehot(a1) (bn, sqrt_k) @ d1^T (sqrt_k, bq) —
which is guaranteed-lowerable, MXU-aligned, and keeps the inner loop free of
dynamic addressing. At sqrt_k <= 512 the extra MACs are noise against the MXU
rate while the fusion removes the (N_s, Q, n) intermediates a jnp
implementation materializes in HBM. The tile math is
:func:`repro.kernels.schist.block_sc_scores`, shared with the streaming
kernels; ``taus`` enter query-major like theirs.

Inputs pre-padded: Q to bq, n to bn, sqrt_k to lane multiples (padded
distance columns are never selected because assignments stay < sqrt_k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.schist import LANES, block_sc_scores, query_major


def _scscore_kernel(d1_ref, d2_ref, a1_ref, a2_ref, tau_ref, o_ref, *, n_sub: int):
    bq, bn = o_ref.shape
    o_ref[...] = block_sc_scores(d1_ref, d2_ref, a1_ref, a2_ref, tau_ref,
                                 n_sub=n_sub, bq=bq, bn=bn)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def scscore_pallas(
    d1s: jax.Array,  # (N_s, Q, sqrt_k)
    d2s: jax.Array,
    a1s: jax.Array,  # (N_s, n) int32
    a2s: jax.Array,
    taus: jax.Array,  # (N_s, Q); laid out query-major here
    *,
    bq: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    n_sub, q, sqrt_k = d1s.shape
    n = a1s.shape[1]
    assert q % bq == 0 and n % bn == 0, (d1s.shape, a1s.shape)
    grid = (q // bq, n // bn)
    taus = query_major(taus, jnp.float32)
    return pl.pallas_call(
        functools.partial(_scscore_kernel, n_sub=n_sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
    )(d1s, d2s, a1s, a2s, taus)
