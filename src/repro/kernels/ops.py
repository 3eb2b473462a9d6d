"""jit'd public wrappers around the Pallas kernels.

Each op pads inputs to kernel block multiples (padding schemes chosen so the
math stays exact — see each kernel's docstring), invokes the kernel, and
slices the result back. ``impl`` selects:

  'auto'   — compiled Pallas on TPU, pure-jnp oracle elsewhere (CPU interpret
             mode is a correctness tool, not a performance path),
  'pallas' — force the kernel (interpret=True off-TPU; used by kernel tests),
  'jnp'    — force the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.l2dist import l2dist_pallas
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.masked_rerank import (
    finalize_topk,
    masked_rerank_pallas,
    masked_rerank_stream,
)
from repro.kernels.schist import schist_pallas, schist_stream
from repro.kernels.scscore import scscore_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if impl == "auto":
        return (True, False) if _on_tpu() else (False, False)
    if impl == "pallas":
        return True, not _on_tpu()
    if impl == "jnp":
        return False, False
    raise ValueError(f"unknown impl {impl!r}")


def _pad_axis(x, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def l2dist(x: jax.Array, y: jax.Array, impl: str = "auto") -> jax.Array:
    """Squared L2 distance matrix (M, N) between rows of x (M,d), y (N,d)."""
    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        return ref.l2dist_ref(x, y)
    m, n = x.shape[0], y.shape[0]
    bm = bn = 128
    bk = 128
    xp = _pad_axis(_pad_axis(x.astype(jnp.float32), 0, bm), 1, bk)
    yp = _pad_axis(_pad_axis(y.astype(jnp.float32), 0, bn), 1, bk)
    out = l2dist_pallas(xp, yp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def kmeans_assign(x: jax.Array, c: jax.Array, impl: str = "auto"):
    """(assignments (n,) int32, min sq dist (n,) f32)."""
    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        return ref.kmeans_assign_ref(x, c)
    n, k = x.shape[0], c.shape[0]
    bn = 256
    xp = _pad_axis(_pad_axis(x.astype(jnp.float32), 0, bn), 1, 128)
    cp = _pad_axis(c.astype(jnp.float32), 1, 128)
    cp = _pad_axis(cp, 0, 128, value=1e15)  # padded centroids never win
    a, d = kmeans_assign_pallas(xp, cp, bn=bn, interpret=interpret)
    return a[:n], d[:n]


def scscore(d1s, d2s, a1s, a2s, taus, impl: str = "auto") -> jax.Array:
    """Fused SC-score accumulation (Q, n); see kernels/scscore.py."""
    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        return ref.scscore_ref(d1s, d2s, a1s, a2s, taus)
    _n_sub, q, _sk = d1s.shape
    n = a1s.shape[1]
    bq, bn = 8, 512
    d1p = _pad_axis(_pad_axis(d1s.astype(jnp.float32), 1, bq), 2, 128)
    d2p = _pad_axis(_pad_axis(d2s.astype(jnp.float32), 1, bq), 2, 128)
    a1p = _pad_axis(a1s.astype(jnp.int32), 1, bn)
    a2p = _pad_axis(a2s.astype(jnp.int32), 1, bn)
    taup = _pad_axis(taus.astype(jnp.float32), 1, bq)
    out = scscore_pallas(d1p, d2p, a1p, a2p, taup, bq=bq, bn=bn, interpret=interpret)
    return out[:q, :n]


def flash_attention(q, k, v, causal: bool = True, impl: str = "auto"):
    """Fused softmax attention (BH, S, hd) — scores never reach HBM."""
    from repro.kernels.flash_attention import flash_attention_pallas

    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal)
    s, t = q.shape[1], k.shape[1]
    bq = min(128, s)
    bk = min(128, t)
    qp = _pad_axis(q, 1, bq)
    kp = _pad_axis(k, 1, bk)
    vp = _pad_axis(v, 1, bk)
    # Padded key columns are masked to -inf inside the kernel (t_valid), so
    # non-bk-divisible T is exact for causal AND non-causal attention; padded
    # query rows compute garbage that the slice below drops.
    out = flash_attention_pallas(qp, kp, vp, causal=causal, bq=bq, bk=bk,
                                 t_valid=t, interpret=interpret)
    return out[:, :s]


def schist(d1s, d2s, a1s, a2s, taus, impl: str = "auto",
           block: int = 4096,
           blocks: tuple[int, int] | None = None) -> jax.Array:
    """Streaming fused SC-score histogram (Q, N_s+1) int32 — the (Q, n) SC
    matrix never materializes; see kernels/schist.py.

    ``blocks`` overrides the Pallas (bq, bn) tile sizes; when None the
    autotune cache is consulted (DEFAULT_BLOCKS if this shape was never
    tuned — see kernels/autotune.py)."""
    n_levels = d1s.shape[0] + 1
    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        return schist_stream(d1s, d2s, a1s, a2s, taus, n_levels=n_levels,
                             block=block)
    _n_sub, q, _sk = d1s.shape
    n = a1s.shape[1]
    bq, bn = blocks or autotune.get_blocks("schist", q=q, n=n)
    d1p = _pad_axis(_pad_axis(d1s.astype(jnp.float32), 1, bq), 2, 128)
    d2p = _pad_axis(_pad_axis(d2s.astype(jnp.float32), 1, bq), 2, 128)
    a1p = _pad_axis(a1s.astype(jnp.int32), 1, bn)
    a2p = _pad_axis(a2s.astype(jnp.int32), 1, bn)
    taup = _pad_axis(taus.astype(jnp.float32), 1, bq)
    out = schist_pallas(d1p, d2p, a1p, a2p, taup, n_levels=n_levels,
                        n_valid=n, bq=bq, bn=bn, interpret=interpret)
    return out[:q, :n_levels]


def masked_rerank(d1s, d2s, a1s, a2s, taus, thresh, data, data_norms,
                  queries, k: int, impl: str = "auto", block: int = 4096,
                  blocks: tuple[int, int] | None = None,
                  precision: str = "f32", counts: bool = False):
    """Streaming masked full-matmul re-rank: ((Q, k) ids i32, (Q, k) exact
    sq dists f32), no candidate cap and no (Q, n)/(Q, cap, d) intermediate;
    see kernels/masked_rerank.py.

    ``counts=True`` appends the Pallas kernel's grid-step counts, an int32
    ``(merged, run)`` pair: the steps that merged their point block into
    the running top-k, and all steps. The jnp path appends None.

    ``blocks`` overrides the Pallas (bq, bn) tile sizes (autotune cache
    consulted when None). ``precision="bf16"`` streams bfloat16 query/data
    tiles (f32 accumulation): the Pallas path stores actual bf16 buffers
    (the kernel upcasts per tile), the jnp path rounds the same operands
    through bf16 — both select candidates from identical rounded math, and
    finalize_topk recomputes the returned distances in exact f32 either
    way."""
    use_pallas, interpret = _resolve(impl)
    if not use_pallas:
        bd, bi = masked_rerank_stream(
            d1s, d2s, a1s, a2s, taus, thresh, queries, data, data_norms,
            k=k, block=block, precision=precision,
        )
        ids, dists = finalize_topk(bd, bi, data, queries, k)
        return (ids, dists, None) if counts else (ids, dists)
    _n_sub, q, _sk = d1s.shape
    n = data.shape[0]
    bq, bn = blocks or autotune.get_blocks("masked_rerank", precision,
                                           q=q, n=n)
    if precision == "bf16":
        # bf16 tiles pack (16, 128) per sublane-register: keep bq at the
        # native packing to avoid sub-tile strided loads.
        bq = max(bq, 16)
    d1p = _pad_axis(_pad_axis(d1s.astype(jnp.float32), 1, bq), 2, 128)
    d2p = _pad_axis(_pad_axis(d2s.astype(jnp.float32), 1, bq), 2, 128)
    a1p = _pad_axis(a1s.astype(jnp.int32), 1, bn)
    a2p = _pad_axis(a2s.astype(jnp.int32), 1, bn)
    taup = _pad_axis(taus.astype(jnp.float32), 1, bq)
    thp = _pad_axis(thresh.astype(jnp.int32), 0, bq)
    qp = _pad_axis(_pad_axis(queries.astype(jnp.float32), 0, bq), 1, 128)
    xp = _pad_axis(_pad_axis(data.astype(jnp.float32), 0, bn), 1, 128)
    nrmp = _pad_axis(data_norms.astype(jnp.float32), 0, bn)
    if precision == "bf16":
        qp = qp.astype(jnp.bfloat16)
        xp = xp.astype(jnp.bfloat16)
    bd, bi, merged = masked_rerank_pallas(
        d1p, d2p, a1p, a2p, taup, thp, qp, xp, nrmp,
        k=k, n_valid=n, bq=bq, bn=bn, interpret=interpret,
    )
    ids, dists = finalize_topk(bd[:q], bi[:q], data, queries, k)
    if not counts:
        return ids, dists
    row_counts = merged[::bq, 0]
    steps = row_counts.shape[0] * (xp.shape[0] // bn)
    return ids, dists, jnp.stack([jnp.sum(row_counts), jnp.int32(steps)])
