"""Fused masked full-matmul re-rank Pallas kernel (streaming pass 2 of the
masked-full query pipeline).

Per (query block, point block) grid step the kernel

  1. recomputes the block's SC-scores in VMEM (one-hot matmul, identical
     to ``kernels.scscore``/``kernels.schist``),
  2. computes exact squared distances by matmul —
     ``||q||^2 - 2 q.X^T + ||x||^2`` with ``||x||^2`` precomputed once at
     index build time (``SCIndex.data_norms``) — an MXU-shaped contraction
     instead of the gather path's (Q, cap, d) candidate gather,
  3. masks distances of points below the per-query SC threshold (and of
     padding) to +inf, and
  4. merges the block into a running per-query top-k state carried in VMEM
     scratch across the point-block grid axis (flash-attention-style
     streaming accumulator: bitonic partial sort of the block, then one
     sorted-run merge against the state — O(log^2 bn + log kp) vectorized
     compare-exchange passes instead of the old k rounds of extract-min,
     which scaled linearly with k) — but only when some distance of the
     block beats the state's k-th best (the skip rule below).

No candidate set is ever materialized and there is no static candidate
cap, so truncation is structurally impossible: every point at or above
the Alg. 5 threshold competes for the top-k, exactly as the paper's
dynamic-shape algorithm.

Streaming-accumulator design notes
----------------------------------
* Block sizes: ``bq`` queries x ``bn`` points; point blocks are the inner
  grid axis. Scratch ``(bq, kp)`` best-distance/best-id tiles persist
  across that axis (kp = k padded to a 128-lane tile); outputs are written
  once, at the last point block.
* Padding scheme: padded point columns (global index >= ``n_valid``) are
  masked to +inf BEFORE the merge, so they can never enter the top-k
  state; padded query rows produce garbage that the wrapper slices off;
  padded sqrt_k distance columns are never selected (assignments stay
  < sqrt_k); the feature dim is zero-padded (exact for dot products).
* Tie handling: every compare-exchange uses the compound (distance, id)
  key, so distance ties resolve to the lowest point id. Because point
  blocks stream in ascending-id order, this is exactly the old
  keep-the-incumbent extract-min rule (the incumbent always has the lower
  id), and the same rule as the gather path's stable top_k over
  index-ordered candidates. The wrapper canonicalizes the final slot
  order (distance-major, id-minor) for bitwise-stable results.
* State layout: the (bq, kp) scratch is kept sorted ascending by
  (distance, id). Unfilled slots hold (+inf, -1); masked/padded points
  carry (+inf, real id), which the compound order places AFTER every
  (+inf, -1), so they can never displace an empty slot — the first k
  lanes are always the k best (or (+inf, -1) when fewer points pass).
  The merge keeps kp entries, but only lanes < k are ever read:
  ``finalize_topk`` slices ``[:, :k]``. Lanes k..kp-1 hold real entries
  of earlier blocks (or (+inf, -1)), each no smaller than lane k-1, and
  not necessarily the (k+1)-th..kp-th best.
* Skip rule: a grid step merges its block only when some masked distance
  is strictly below the state's k-th best, ``bd[:, k-1]``; otherwise the
  sort and merge are skipped and the state is left as it was. That returns
  the same first k lanes: the k smallest of state ∪ block are the k
  smallest of state[:k] ∪ block, and a block entry can only displace the
  k-th entry with a strictly smaller distance, since blocks stream in
  ascending id and an equal distance resolves to the incumbent's lower
  id. Masked and padded entries are +inf and never pass ``< kth``, even
  while kth is still +inf. Corpus order is random with respect to a
  query, so once the state is full only a few percent of blocks hold any
  distance below the k-th best. Each query block counts the steps that
  merged; the count is the kernel's third output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.schist import (
    LANES,
    _block_sc,
    _shrink_to_divisor,
    block_sc_scores,
    cell_ids,
    collision_table,
    query_major,
)

INF = float("inf")  # plain Python float: jnp scalars would be captured
                    # as pallas_call constants


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _partner(x, lane, stride: int):
    """Value at ``lane XOR stride`` — the bitonic exchange partner — via two
    lane rotations + select (``pltpu.roll`` lowers on Mosaic; reshapes that
    split the lane axis may not). No wraparound leaks: a lane with bit
    ``stride`` clear reads lane+stride (< L), one with it set reads
    lane-stride (>= 0)."""
    L = x.shape[1]
    up = pltpu.roll(x, L - stride, 1)  # y[lane] = x[lane + stride]
    dn = pltpu.roll(x, stride, 1)      # y[lane] = x[lane - stride]
    return jnp.where((lane & stride) == 0, up, dn)


def _compare_exchange(d, i, lane, stride: int, asc):
    """One bitonic compare-exchange pass on the compound (distance, id)
    key. ``asc`` is a per-lane int32 0/1 direction: 1 where the enclosing
    subsequence sorts ascending (partners always agree — they differ only
    in bit ``stride``, below any direction bit).

    Lane bookkeeping stays in int32 and only float/int payloads are
    selected: Mosaic cannot lower a bool-valued select or a bool == bool
    compare (it truncates an i8 vector to i1)."""
    dp = _partner(d, lane, stride)
    ip = _partner(i, lane, stride)
    partner_less = jnp.where((dp < d) | ((dp == d) & (ip < i)), 1, 0)
    # the low lane of an ascending pair (and the high lane of a descending
    # one) takes the partner when the partner is smaller
    hi_bit = jnp.where((lane & stride) == 0, 0, 1)
    take = (partner_less ^ asc ^ hi_bit) == 0
    return jnp.where(take, dp, d), jnp.where(take, ip, i)


def _bitonic_sort(d, i, lane, *, descending: bool = False):
    """Full bitonic sort of each row by the compound (distance, id) key.
    Lane count must be a power of two."""
    L = d.shape[1]
    size = 2
    while size <= L:
        asc = jnp.where((lane & size) == 0, 1, 0)
        if descending:
            asc = 1 - asc
        stride = size // 2
        while stride:
            d, i = _compare_exchange(d, i, lane, stride, asc)
            stride //= 2
        size *= 2
    return d, i


def _merge_topk(bd, bi, dist, ids_base):
    """Merge (bq, bn) block distances into the (bq, kp) running state.

    The state is kept fully sorted ascending by (distance, id). The block
    is bitonic-sorted DESCENDING; its kp smallest entries (the last kp
    lanes, a descending run) then concatenate with the ascending state
    into a bitonic sequence, so one elementwise compound-min plus log2(kp)
    merge passes yields the sorted kp smallest of state ∪ block —
    O(log^2 bn) passes total, independent of k (the old extract-min merge
    paid 4 reduction passes per result slot).
    """
    bq, kp = bd.shape
    bn = dist.shape[1]
    ids = ids_base + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    # pad lanes to a power of two (>= kp) with (+inf, INT32_MAX): the
    # compound-largest entry, so padding can never beat a real slot
    L = max(_next_pow2(bn), kp)
    if L != bn:
        dist = jnp.concatenate(
            [dist, jnp.full((bq, L - bn), INF, dist.dtype)], axis=1)
        ids = jnp.concatenate(
            [ids, jnp.full((bq, L - bn), jnp.int32(2**31 - 1))], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, L), 1)
    dist, ids = _bitonic_sort(dist, ids, lane, descending=True)
    bd_blk = dist[:, L - kp:]  # kp smallest of the block, descending
    bi_blk = ids[:, L - kp:]
    # ascending state ++ descending block is bitonic: elementwise
    # compound-min is the first merge stage and keeps the kp smallest
    blk_less = (bd_blk < bd) | ((bd_blk == bd) & (bi_blk < bi))
    d = jnp.where(blk_less, bd_blk, bd)
    i = jnp.where(blk_less, bi_blk, bi)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)
    asc = jnp.ones((bq, kp), jnp.int32)
    stride = kp // 2
    while stride:
        d, i = _compare_exchange(d, i, lane_k, stride, asc)
        stride //= 2
    return d, i


def _masked_rerank_kernel(
    d1_ref, d2_ref, a1_ref, a2_ref, tau_ref, th_ref, q_ref, x_ref, nrm_ref,
    od_ref, oi_ref, om_ref, bd_scr, bi_scr, nm_scr, *, k: int, n_sub: int,
    n_valid: int, bn: int, n_blocks: int
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_scr[...] = jnp.full_like(bd_scr, INF)
        bi_scr[...] = jnp.full_like(bi_scr, -1)
        nm_scr[...] = jnp.zeros_like(nm_scr)

    bq = od_ref.shape[0]
    sc = block_sc_scores(d1_ref, d2_ref, a1_ref, a2_ref, tau_ref,
                         n_sub=n_sub, bq=bq, bn=bn)

    # --- exact squared distances by matmul --------------------------------
    q = q_ref[...].astype(jnp.float32)  # (bq, d)
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    qdot = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (bq, bn)
    qn = jnp.sum(q * q, axis=1)
    dist = jnp.maximum(qn[:, None] - 2.0 * qdot + nrm_ref[...], 0.0)

    # --- threshold + padding mask, then streaming top-k merge -------------
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    keep = (sc >= th_ref[:, 0:1]) & (col < n_valid)
    dist = jnp.where(keep, dist, INF)
    # skip rule (module notes): merge only a block that can change the
    # first k lanes
    kth = bd_scr[:, k - 1:k]
    beats_kth = jnp.max(jnp.where(dist < kth, 1, 0)) > 0

    @pl.when(beats_kth)
    def _merge():
        bd, bi = _merge_topk(bd_scr[...], bi_scr[...], dist, j * bn)
        bd_scr[...] = bd
        bi_scr[...] = bi
        nm_scr[...] += 1

    @pl.when(j == n_blocks - 1)
    def _finish():
        od_ref[...] = bd_scr[...]
        oi_ref[...] = bi_scr[...]
        om_ref[...] = nm_scr[...]


@functools.partial(
    jax.jit, static_argnames=("k", "n_valid", "bq", "bn", "interpret")
)
def masked_rerank_pallas(
    d1s: jax.Array,  # (N_s, Q, sqrt_k) pre-padded
    d2s: jax.Array,
    a1s: jax.Array,  # (N_s, n) int32 pre-padded
    a2s: jax.Array,
    taus: jax.Array,  # (N_s, Q); taus and thresh are laid out query-major
    thresh: jax.Array,  # (Q,) int32
    queries: jax.Array,  # (Q, d) pre-padded
    data: jax.Array,  # (n, d) pre-padded
    data_norms: jax.Array,  # (n,)
    *,
    k: int,
    n_valid: int,
    bq: int = 8,
    bn: int = 512,
    interpret: bool = False,
):
    """Per-query top-k state and merge count: ((Q, kp) dists f32, (Q, kp)
    ids i32, (Q, 128) merged i32). The first k lanes of the state are the
    top-k, ascending by (distance, id) (id -1 / +inf when fewer than k
    points pass the threshold); lanes >= k are not part of the answer.
    Every entry of a query block's rows of ``merged`` holds the number of
    point blocks that block merged (the skip rule's misses did not), so
    ``merged[::bq, 0]`` is one count per grid row. ``bq``/``bn`` that
    do not divide Q/n are auto-shrunk to the largest divisor instead of
    crashing (direct callers with odd shapes; the padded ``ops`` wrappers
    always pass divisible shapes)."""
    n_sub, q, sqrt_k = d1s.shape
    n, d = data.shape
    bq = _shrink_to_divisor(q, bq)
    bn = _shrink_to_divisor(n, bn)
    kp = max(128, _next_pow2(k))
    n_blocks = n // bn
    grid = (q // bq, n_blocks)
    taus = query_major(taus, jnp.float32)
    thresh = query_major(thresh, jnp.int32)
    # a 1-D (bn,) block does not match XLA's 1-D tiling on TPU; a (1, bn)
    # row of a (1, n) array does
    data_norms = data_norms.reshape(1, n)
    return pl.pallas_call(
        functools.partial(
            _masked_rerank_kernel, k=k, n_sub=n_sub, n_valid=n_valid,
            bn=bn, n_blocks=n_blocks,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bq, sqrt_k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((n_sub, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, kp), jnp.float32),
            jax.ShapeDtypeStruct((q, kp), jnp.int32),
            jax.ShapeDtypeStruct((q, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
            pltpu.VMEM((bq, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(d1s, d2s, a1s, a2s, taus, thresh, queries, data, data_norms)


# ---------------------------------------------------------------------------
# Streaming jnp path — same blockwise discipline via lax.fori_loop; the loop
# carry is the (Q, k) running top-k, so no (Q, n) or (Q, cap, d) intermediate
# exists on this path either (it is the CPU serving path, not just a test
# oracle).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "block", "precision"))
def masked_rerank_stream(
    d1s: jax.Array,
    d2s: jax.Array,
    a1s: jax.Array,
    a2s: jax.Array,
    taus: jax.Array,
    thresh: jax.Array,
    queries: jax.Array,
    data: jax.Array,
    data_norms: jax.Array,
    *,
    k: int,
    block: int = 4096,
    precision: str = "f32",
):
    """Running top-k over n-blocks: ((Q, k) dists, (Q, k) ids), unsorted
    beyond ascending-distance order from the per-block top_k merge.

    ``precision="bf16"`` rounds the matmul operands (queries once, each
    data block inside the loop) through bfloat16 with f32 accumulation —
    the same math as the Pallas kernel streaming bf16 tiles, so the two
    paths stay bitwise-comparable at either precision. ``data_norms`` stay
    exact f32 on both paths."""
    n_sub, qn_, sqrt_k = d1s.shape
    n, d = data.shape
    table = collision_table(d1s, d2s, taus)
    cells = cell_ids(a1s, a2s, sqrt_k)
    block = min(block, max(n, 1))
    pad = (-n) % block
    cells = jnp.pad(cells, ((0, 0), (0, pad)))
    data_p = jnp.pad(data.astype(jnp.float32), ((0, pad), (0, 0)))
    norms_p = jnp.pad(data_norms.astype(jnp.float32), (0, pad))
    n_blocks = cells.shape[1] // block
    queries = queries.astype(jnp.float32)
    if precision == "bf16":
        queries = queries.astype(jnp.bfloat16).astype(jnp.float32)
    q_norms = jnp.sum(queries * queries, axis=1)

    def body(b, carry):
        best_d, best_i = carry
        lo = b * block
        cells_blk = jax.lax.dynamic_slice(cells, (0, lo), (n_sub, block))
        sc = _block_sc(table, cells_blk)
        x = jax.lax.dynamic_slice(data_p, (lo, 0), (block, d))
        if precision == "bf16":
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        nrm = jax.lax.dynamic_slice(norms_p, (lo,), (block,))
        qdot = jnp.matmul(queries, x.T, precision=jax.lax.Precision.HIGHEST)
        dist = jnp.maximum(q_norms[:, None] - 2.0 * qdot + nrm[None, :], 0.0)
        ids = lo + jnp.arange(block, dtype=jnp.int32)
        keep = (sc >= thresh[:, None]) & (ids < n)[None, :]
        dist = jnp.where(keep, dist, jnp.inf)
        cmb_d = jnp.concatenate([best_d, dist], axis=1)
        cmb_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, sc.shape)], axis=1
        )
        neg, pos = jax.lax.top_k(-cmb_d, k)
        return -neg, jnp.take_along_axis(cmb_i, pos, axis=1)

    best_d0 = jnp.full((queries.shape[0], k), jnp.inf, jnp.float32)
    best_i0 = jnp.full((queries.shape[0], k), -1, jnp.int32)
    return jax.lax.fori_loop(0, n_blocks, body, (best_d0, best_i0))


def finalize_topk(best_d, best_i, data, queries, k: int):
    """Canonicalize + exactify a streamed top-k state.

    Sorts the k slots distance-major / id-minor (two stable argsorts), maps
    empty slots to id -1, then recomputes the returned squared distances
    exactly from the original vectors — a (Q, k, d) gather, the only gather
    in the whole masked pipeline.
    """
    best_d = best_d[:, :k]
    best_i = best_i[:, :k]
    o1 = jnp.argsort(best_i, axis=1, stable=True)
    d1 = jnp.take_along_axis(best_d, o1, axis=1)
    i1 = jnp.take_along_axis(best_i, o1, axis=1)
    o2 = jnp.argsort(d1, axis=1, stable=True)
    ids = jnp.take_along_axis(i1, o2, axis=1)
    filled = jnp.isfinite(jnp.take_along_axis(d1, o2, axis=1))
    ids = jnp.where(filled, ids, -1)
    vecs = jnp.take(data, jnp.maximum(ids, 0), axis=0)  # (Q, k, d)
    diff = vecs - queries[:, None, :]
    dists = jnp.where(ids >= 0, jnp.sum(diff * diff, axis=-1), jnp.inf)
    return ids, dists
