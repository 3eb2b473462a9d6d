"""Fused SC-score + histogram Pallas kernel (streaming pass 1 of the
masked-full query pipeline).

For each (query block, point block) the kernel recomputes the block's
SC-scores in VMEM — the same one-hot-matmul collision counting as
``kernels.scscore`` — and immediately folds them into the per-query
SC-score histogram. The histogram is the kernel's only output: the grid
iterates point blocks innermost and accumulates into a revisited
(bq, level-width) output block (flash-attention-style streaming
accumulator), so the (Q, n) SC matrix never reaches HBM. Downstream,
Algorithm 5 (and the fixed-budget SuCo cut) need only this histogram to
pick the re-rank threshold.

Streaming-accumulator design notes
----------------------------------
* Block sizes: ``bq`` queries x ``bn`` points per grid step; ``bn`` is the
  streamed axis. The output block index map pins every ``j`` to the same
  (bq, hw) tile, which therefore stays VMEM-resident across the inner
  grid axis — initialized at ``j == 0``, accumulated into thereafter.
* Padding scheme: Q is padded to ``bq`` (garbage histogram rows, sliced
  off by the wrapper); n is padded to ``bn``. Padded points CANNOT enter
  the histogram: the kernel masks on the global column index
  ``j*bn + lane < n_valid`` before counting, so a padded point's
  (assignment-0-gathered) SC value is never accumulated. sqrt_k is padded
  to lane multiples — padded distance columns are never selected because
  real assignments stay ``< sqrt_k``.
* The level axis (N_s+1 <= ~7 buckets) is padded to one 128-lane tile;
  the wrapper slices the real levels back out.
* TPU tiling: every block's last two dims must be multiples of (8, 128)
  or span the whole array. The per-(subspace, query) thresholds therefore
  enter query-major and lane-padded, ``(Q, 128)`` with subspace ``s`` in
  lane ``s`` (:func:`query_major`), so a query block is one ``(bq, 128)``
  tile at any bucket size.
* Precision: the one-hot collision matmul is exact only at f32 contract
  precision (a single bf16 pass would round the centroid distances), so it
  runs at ``Precision.HIGHEST`` on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Lane width of one TPU vreg tile; query-major per-query inputs are padded
#: to it so their blocks tile at any query-block size.
LANES = 128


def _shrink_to_divisor(total: int, b: int) -> int:
    """Largest block size <= b that divides total (>= 1): lets direct kernel
    callers use odd shapes without pre-padding — the block simply shrinks
    instead of the old hard divisibility assert crashing."""
    b = max(1, min(b, total))
    while total % b:
        b -= 1
    return b


def query_major(x: jax.Array, dtype) -> jax.Array:
    """(N_s, Q) per-subspace rows -> (Q, LANES) query-major, zero-padded
    lanes (subspace ``s`` in lane ``s``); a (Q,) vector becomes lane 0."""
    x = jnp.asarray(x, dtype)
    x = x[:, None] if x.ndim == 1 else x.T
    assert x.shape[1] <= LANES, x.shape
    return jnp.pad(x, ((0, 0), (0, LANES - x.shape[1])))


def block_sc_scores(d1_ref, d2_ref, a1_ref, a2_ref, tau_ref, *, n_sub: int,
                    bq: int, bn: int) -> jax.Array:
    """In-kernel (bq, bn) SC-score tile via the one-hot-matmul collision
    count. Shared by the scscore, schist and masked_rerank kernels so pass
    1's histogram and pass 2's mask can never diverge. ``tau_ref`` is the
    query-major (bq, LANES) threshold tile."""
    sc = jnp.zeros((bq, bn), jnp.int32)
    sqrt_k = d1_ref.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, sqrt_k), 1)
    for s in range(n_sub):
        d1 = d1_ref[s].astype(jnp.float32)  # (bq, sqrt_k)
        d2 = d2_ref[s].astype(jnp.float32)
        a1 = a1_ref[s]  # (bn,)
        a2 = a2_ref[s]
        oh1 = (a1[:, None] == iota).astype(jnp.float32)  # (bn, sqrt_k)
        oh2 = (a2[:, None] == iota).astype(jnp.float32)
        s1 = jax.lax.dot_general(
            oh1, d1, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (bn, bq)
        s2 = jax.lax.dot_general(
            oh2, d2, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        tau = tau_ref[:, s:s + 1]  # (bq, 1)
        sc = sc + jnp.where((s1 + s2).T <= tau, 1, 0)
    return sc


def _schist_kernel(
    d1_ref, d2_ref, a1_ref, a2_ref, tau_ref, o_ref, *, n_sub: int, n_levels: int,
    n_valid: int, bn: int
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bq = o_ref.shape[0]
    sc = block_sc_scores(d1_ref, d2_ref, a1_ref, a2_ref, tau_ref,
                         n_sub=n_sub, bq=bq, bn=bn)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    valid = col < n_valid
    lev = jax.lax.broadcasted_iota(jnp.int32, (bq, o_ref.shape[1]), 1)
    acc = o_ref[...]
    for l in range(n_levels):
        cnt = jnp.sum(jnp.where(valid & (sc == l), 1, 0), axis=1)  # (bq,)
        acc = acc + jnp.where(lev == l, cnt[:, None], 0)
    o_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("n_levels", "n_valid", "bq", "bn", "interpret")
)
def schist_pallas(
    d1s: jax.Array,  # (N_s, Q, sqrt_k) pre-padded
    d2s: jax.Array,
    a1s: jax.Array,  # (N_s, n) int32 pre-padded
    a2s: jax.Array,
    taus: jax.Array,  # (N_s, Q); laid out query-major here
    *,
    n_levels: int,
    n_valid: int,
    bq: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-query SC-score histogram (Q, hw) with hw one lane tile wide;
    real counts live in columns [0, n_levels). Non-divisible ``bq``/``bn``
    auto-shrink to the largest divisor (see :func:`_shrink_to_divisor`)."""
    n_sub, q, sqrt_k = d1s.shape
    n = a1s.shape[1]
    bq = _shrink_to_divisor(q, bq)
    bn = _shrink_to_divisor(n, bn)
    assert n_levels <= 128, n_levels
    hw = 128
    grid = (q // bq, n // bn)  # point blocks innermost: o block revisited
    taus = query_major(taus, jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _schist_kernel, n_sub=n_sub, n_levels=n_levels, n_valid=n_valid, bn=bn
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, hw), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, hw), jnp.int32),
        interpret=interpret,
    )(d1s, d2s, a1s, a2s, taus)


# ---------------------------------------------------------------------------
# Streaming jnp path — the exact same blockwise accumulation, expressed as a
# lax.fori_loop for backends without a Pallas lowering (the CPU serving
# path). Keeps the no-(Q, n)-intermediate guarantee: the loop carry is the
# (Q, N_s+1) histogram and each block's SC tile dies with its iteration.
# ---------------------------------------------------------------------------


def collision_table(d1s, d2s, taus):
    """Per-(subspace, query, IMI cell) collision bits: (N_s, Q, sqrt_k^2).

    SC counting over a block then becomes ONE int gather per subspace
    (``table[s][:, cell_ids]``) instead of two float gathers + add +
    compare — the sqrt_k^2 (<= ~1024) cell combinations are enumerated once
    per query. Bitwise-identical to the per-point test: the compared sum
    ``d1[c1] + d2[c2]`` is the same two floats either way.
    """
    n_sub, q, sqrt_k = d1s.shape
    table = (d1s[:, :, :, None] + d2s[:, :, None, :]) <= taus[:, :, None, None]
    return table.astype(jnp.int32).reshape(n_sub, q, sqrt_k * sqrt_k)


def cell_ids(a1s, a2s, sqrt_k: int) -> jax.Array:
    """Combined IMI cell index per (subspace, point): (N_s, n) int32."""
    return (a1s.astype(jnp.int32) * sqrt_k + a2s.astype(jnp.int32))


def _block_sc(table, cells_blk):
    """(Q, bn) SC-scores of one point block from the collision table."""
    n_sub = table.shape[0]
    sc = jnp.zeros((table.shape[1], cells_blk.shape[1]), jnp.int32)
    for s in range(n_sub):
        sc = sc + jnp.take(table[s], cells_blk[s], axis=1)
    return sc


@functools.partial(jax.jit, static_argnames=("n_levels", "block"))
def schist_stream(
    d1s: jax.Array,
    d2s: jax.Array,
    a1s: jax.Array,
    a2s: jax.Array,
    taus: jax.Array,
    *,
    n_levels: int,
    block: int = 4096,
) -> jax.Array:
    """(Q, n_levels) int32 per-query SC histogram, streamed over n-blocks."""
    n_sub, q, sqrt_k = d1s.shape
    n = a1s.shape[1]
    table = collision_table(d1s, d2s, taus)
    cells = cell_ids(a1s, a2s, sqrt_k)
    block = min(block, max(n, 1))
    pad = (-n) % block
    cells = jnp.pad(cells, ((0, 0), (0, pad)))
    n_blocks = cells.shape[1] // block

    def body(b, hist):
        lo = b * block
        cells_blk = jax.lax.dynamic_slice(cells, (0, lo), (n_sub, block))
        sc = _block_sc(table, cells_blk)
        valid = (lo + jnp.arange(block, dtype=jnp.int32)) < n
        counts = [
            jnp.sum(valid[None, :] & (sc == l), axis=1) for l in range(n_levels)
        ]
        return hist + jnp.stack(counts, axis=1).astype(jnp.int32)

    hist0 = jnp.zeros((q, n_levels), jnp.int32)
    return jax.lax.fori_loop(0, n_blocks, body, hist0)
