"""Streaming masked-full re-rank pipeline (ISSUE 3): kernel-vs-oracle
sweeps for schist / masked_rerank, masked ≡ gather equivalence whenever the
gather path does not truncate, and exact dynamic-shape Algorithm 5 semantics
where it does.

Equivalence tests use integer-valued vectors: squared distances are then
exactly representable in float32 no matter the formulation (diff-square vs
||q||^2 - 2q.x + ||x||^2, blockwise vs monolithic), so id comparisons are
bitwise-deterministic instead of ulp-tie flaky.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import build, query_with_stats, taco_config
from repro.core.config import resolve_rerank, suco_config
from repro.core.selection import _alg5_threshold_reference, fixed_budget
from repro.core.taco import compute_sc_scores
from repro.kernels import ops, ref
from repro.kernels.masked_rerank import finalize_topk, masked_rerank_stream
from repro.kernels.schist import schist_stream


def _int_dataset(rng, n, d, q, lo=-10, hi=11):
    data = rng.integers(lo, hi, (n, d)).astype(np.float32)
    queries = rng.integers(lo, hi, (q, d)).astype(np.float32)
    return data, queries


def _case(rng, n_sub, q, sqrt_k, n, d=16):
    d1s = jnp.asarray(rng.uniform(0, 4, (n_sub, q, sqrt_k)), jnp.float32)
    d2s = jnp.asarray(rng.uniform(0, 4, (n_sub, q, sqrt_k)), jnp.float32)
    a1s = jnp.asarray(rng.integers(0, sqrt_k, (n_sub, n)), jnp.int32)
    a2s = jnp.asarray(rng.integers(0, sqrt_k, (n_sub, n)), jnp.int32)
    taus = jnp.asarray(rng.uniform(1, 5, (n_sub, q)), jnp.float32)
    data, queries = _int_dataset(rng, n, d, q, -8, 9)
    norms = jnp.sum(jnp.asarray(data) ** 2, axis=1)
    thresh = jnp.asarray(rng.integers(0, n_sub + 1, (q,)), jnp.int32)
    return d1s, d2s, a1s, a2s, taus, thresh, jnp.asarray(data), norms, jnp.asarray(queries)


# ------------------------------------------------------------ schist kernel
@pytest.mark.parametrize("n_sub,q,sqrt_k,n", [
    (2, 3, 5, 50),      # everything unpadded-odd
    (6, 8, 16, 512),    # block-divisible
    (4, 16, 32, 1030),  # padded n
    (1, 1, 128, 100),
])
def test_schist_pallas_matches_ref(n_sub, q, sqrt_k, n):
    rng = np.random.default_rng(n_sub * 100 + q)
    d1s, d2s, a1s, a2s, taus, *_ = _case(rng, n_sub, q, sqrt_k, n)
    got = ops.schist(d1s, d2s, a1s, a2s, taus, impl="pallas")
    want = ref.schist_ref(d1s, d2s, a1s, a2s, taus, n_sub + 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # every point lands in exactly one bucket — padding can never leak in
    np.testing.assert_array_equal(np.asarray(got).sum(1), n)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(1, 9), st.integers(2, 20),
       st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_schist_stream_property(n_sub, q, sqrt_k, n, seed):
    rng = np.random.default_rng(seed)
    d1s, d2s, a1s, a2s, taus, *_ = _case(rng, n_sub, q, sqrt_k, n)
    got = np.asarray(schist_stream(d1s, d2s, a1s, a2s, taus,
                                   n_levels=n_sub + 1, block=64))
    want = np.asarray(ref.schist_ref(d1s, d2s, a1s, a2s, taus, n_sub + 1))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- masked_rerank kernel
def _ordered_case(rng, layout, q, n, *, n_sub=2, sqrt_k=4, d=16, bn=128):
    """Collision inputs and integer rows whose distance to every query
    follows ``layout`` in id order, so the skip rule's cases can be built:

    * ``ascending`` / ``descending``: distance non-decreasing / non-increasing
      in id; ``random``: a permutation of the ascending rows;
    * ``ties``: a group of 8 rows at one small distance straddling the end
      of the first ``bn`` block, every other row far;
    * ``few``: random order, and only 5 rows can pass a threshold above 0.

    Cell (0, 0) alone collides (0 <= tau 1; every other sum >= 4), so a row
    scores ``n_sub`` when its cells are (0, 0) and 0 otherwise. Thresholds
    are 0 (every row passes), ``n_sub`` (the marked rows) or ``n_sub + 1``
    (none). Every value is exact in bfloat16, every distance in float32."""
    i = np.arange(n)
    r = i * 256 // n  # 0..255, non-decreasing
    marked = rng.random(n) < 0.7
    if layout == "descending":
        r = r[::-1]
    elif layout in ("random", "few"):
        r = rng.permutation(r)
    elif layout == "ties":
        r = 200 + i % 50
        r[bn - 4:bn + 4] = 3
    if layout == "few":
        marked = np.zeros(n, bool)
        marked[rng.choice(n, 5, replace=False)] = True
    data = np.zeros((n, d), np.float32)
    data[:, 0] = r
    queries = np.zeros((q, d), np.float32)
    queries[:, 0] = -rng.integers(1, 4, q)
    queries[:, d // 2:] = rng.integers(-8, 9, (q, d - d // 2))
    d1s = np.full((n_sub, q, sqrt_k), 4.0, np.float32)
    d1s[:, :, 0] = 0.0
    cells = np.where(marked, 0, 1).astype(np.int32)
    a1s = np.broadcast_to(cells, (n_sub, n))
    a2s = np.zeros((n_sub, n), np.int32)
    taus = np.ones((n_sub, q), np.float32)
    thresh = np.where(rng.random(q) < 0.5, 0, n_sub).astype(np.int32)
    if layout == "few":
        thresh[:] = n_sub
        thresh[0] = n_sub + 1
    data, queries = jnp.asarray(data), jnp.asarray(queries)
    return (jnp.asarray(d1s), jnp.asarray(d1s), jnp.asarray(a1s),
            jnp.asarray(a2s), jnp.asarray(taus), jnp.asarray(thresh), data,
            jnp.sum(data * data, axis=1), queries)


# "random" cases draw every input (default blocks); the others stream a few
# thousand rows through (8, 128) blocks in the distance order they name
@pytest.mark.parametrize("n_sub,q,sqrt_k,n,k,layout,precision", [
    pytest.param(2, 3, 5, 50, 5, "random", "f32", id="2-3-5-50-5"),
    pytest.param(6, 8, 16, 512, 10, "random", "f32",  # block-divisible
                 id="6-8-16-512-10"),
    pytest.param(4, 5, 32, 1030, 17, "random", "f32",  # padded n, odd k
                 id="4-5-32-1030-17"),
    pytest.param(3, 1, 8, 40, 40, "random", "f32",  # k == n
                 id="3-1-8-40-40"),
    # only the first blocks merge
    pytest.param(2, 9, 4, 3000, 10, "ascending", "f32", id="ascending-k10"),
    pytest.param(2, 9, 4, 3000, 100, "ascending", "bf16",
                 id="ascending-k100-bf16"),
    # every block merges
    pytest.param(2, 9, 4, 3000, 10, "descending", "f32", id="descending-k10"),
    pytest.param(2, 9, 4, 3000, 1, "descending", "bf16",
                 id="descending-k1-bf16"),
    # equal distances at the k-th slot across a block boundary: the second
    # block's equals are skipped (k 3) or merged behind lower ids (k 6)
    pytest.param(2, 9, 4, 3000, 3, "ties", "f32", id="ties-k3"),
    pytest.param(2, 9, 4, 3000, 6, "ties", "bf16", id="ties-k6-bf16"),
    # fewer than k rows pass: (+inf, -1) slots stay
    pytest.param(2, 9, 4, 3000, 10, "few", "f32", id="few-k10"),
    pytest.param(2, 9, 4, 3000, 100, "few", "bf16", id="few-k100-bf16"),
    pytest.param(2, 9, 4, 3000, 1, "random", "bf16", id="random-k1-bf16"),
])
def test_masked_rerank_pallas_matches_ref(n_sub, q, sqrt_k, n, k, layout,
                                          precision):
    rng = np.random.default_rng(n_sub * 1000 + n + k)
    if layout == "random" and precision == "f32":
        inputs = _case(rng, n_sub, q, sqrt_k, n)
        blocks = None
    else:
        inputs = _ordered_case(rng, layout, q, n, n_sub=n_sub, sqrt_k=sqrt_k)
        blocks = (8, 128)
    d1s, d2s, a1s, a2s, taus, thresh, data, norms, queries = inputs
    gi, gd = ops.masked_rerank(d1s, d2s, a1s, a2s, taus, thresh, data, norms,
                               queries, k, impl="pallas", blocks=blocks,
                               precision=precision)
    wi, wd = ref.masked_rerank_ref(d1s, d2s, a1s, a2s, taus, thresh, queries,
                                   data, norms, k)
    ji, jd = ops.masked_rerank(d1s, d2s, a1s, a2s, taus, thresh, data, norms,
                               queries, k, impl="jnp", precision=precision)
    for oi, od in ((wi, wd), (ji, jd)):
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(gd).view(np.int32),
                                      np.asarray(od).view(np.int32))
    if layout == "few":
        assert (np.asarray(gi) == -1).any(), "no empty slot exercised"


@pytest.mark.parametrize("layout,thresh,merged", [
    ("descending", 0, 16),   # every (query block, point block) step
    ("ascending", 0, 2),     # the first point block of each query block
    ("descending", 3, 0),    # every row masked
])
def test_rerank_merge_counts(layout, thresh, merged):
    """The kernel's count of grid steps that merged, on 16 queries (two
    query blocks) over 1024 rows at strictly monotone distances (eight
    point blocks): 16 steps in all."""
    n, q, n_sub = 1024, 16, 2
    rng = np.random.default_rng(5)
    d1s, d2s, a1s, a2s, taus, _th, _x, _nrm, queries = _ordered_case(
        rng, "ascending", q, n, n_sub=n_sub)
    r = np.arange(n) if layout == "ascending" else np.arange(n)[::-1]
    data = jnp.zeros((n, 16), jnp.float32).at[:, 0].set(r.astype(np.float32))
    norms = jnp.sum(data * data, axis=1)
    thresh = jnp.full((q,), thresh, jnp.int32)
    args = (d1s, d2s, jnp.zeros_like(a1s), a2s, taus, thresh, data, norms,
            queries, 10)
    gi, gd, counts = ops.masked_rerank(*args, impl="pallas", blocks=(8, 128),
                                       counts=True)
    np.testing.assert_array_equal(np.asarray(counts), [merged, 16])
    wi, wd = ref.masked_rerank_ref(d1s, d2s, jnp.zeros_like(a1s), a2s, taus,
                                   thresh, queries, data, norms, 10)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    # the jnp twin runs no grid and counts nothing
    assert ops.masked_rerank(*args, impl="jnp", counts=True)[2] is None


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(1, 6), st.integers(2, 16),
       st.integers(3, 150), st.integers(1, 12), st.integers(0, 2**31 - 1))
def test_masked_rerank_stream_property(n_sub, q, sqrt_k, n, k, seed):
    rng = np.random.default_rng(seed)
    k = min(k, n)
    d1s, d2s, a1s, a2s, taus, thresh, data, norms, queries = _case(
        rng, n_sub, q, sqrt_k, n)
    bd, bi = masked_rerank_stream(d1s, d2s, a1s, a2s, taus, thresh, queries,
                                  data, norms, k=k, block=32)
    gi, gd = finalize_topk(bd, bi, data, queries, k)
    wi, wd = ref.masked_rerank_ref(d1s, d2s, a1s, a2s, taus, thresh, queries,
                                   data, norms, k)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))


# ------------------------------------------------------- end-to-end pipeline
CFG = dict(n_subspaces=3, subspace_dim=6, n_clusters=64, alpha=0.05,
           beta=0.02, k=10)


@pytest.fixture(scope="module")
def int_index():
    rng = np.random.default_rng(7)
    data, queries = _int_dataset(rng, 4000, 32, 8)
    cfg = taco_config(**CFG)
    return build(data, cfg), data, queries


def test_masked_equals_gather_when_not_truncated(int_index):
    """masked_full ≡ gather whenever candidate_demand <= cap (here cap=n)."""
    idx, _data, queries = int_index
    cfg = taco_config(**CFG, candidate_cap=4000)
    gi, gd, gs = query_with_stats(idx, queries, cfg)
    assert not np.asarray(gs["truncated"]).any()
    mi, md, ms = query_with_stats(
        idx, queries, dataclasses.replace(cfg, rerank="masked_full"))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(gi))
    np.testing.assert_allclose(np.asarray(md), np.asarray(gd), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ms["sc_threshold"]),
                                  np.asarray(gs["sc_threshold"]))
    np.testing.assert_array_equal(np.asarray(ms["candidate_demand"]),
                                  np.asarray(gs["candidate_demand"]))
    assert not np.asarray(ms["truncated"]).any()


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_masked_equals_gather_property(seed):
    rng = np.random.default_rng(seed)
    data, queries = _int_dataset(rng, 1500, 24, 4)
    cfg = taco_config(n_subspaces=3, subspace_dim=6, n_clusters=36,
                      alpha=0.1, beta=0.05, k=5, candidate_cap=1500,
                      seed=seed % 97)
    idx = build(data, cfg)
    gi, gd, gs = query_with_stats(idx, queries, cfg)
    assert not np.asarray(gs["truncated"]).any()  # cap == n: can't truncate
    mi, md, _ms = query_with_stats(
        idx, queries, dataclasses.replace(cfg, rerank="masked_full"))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(gi))
    np.testing.assert_allclose(np.asarray(md), np.asarray(gd), rtol=1e-6)


def _dynamic_alg5_oracle(sc_row, data, query, beta_n, n_subspaces, k):
    """Host-side dynamic-shape Algorithm 5 + exact re-rank (float64):
    the ground truth the masked pipeline must match exactly."""
    hist = np.bincount(sc_row, minlength=n_subspaces + 1)
    th = _alg5_threshold_reference(hist, beta_n, n_subspaces)
    cand = np.flatnonzero(sc_row >= th)  # TRUE dynamic-shape candidate set
    d64 = np.sum((data[cand].astype(np.float64) - query) ** 2, axis=1)
    order = np.lexsort((cand, d64))[:k]  # distance-major, id-minor
    return cand[order], d64[order]


def test_masked_exact_where_gather_truncates(int_index):
    """The acceptance case: on inputs where the gather path reports
    truncated=True, masked_full still returns the exact dynamic-shape
    Alg. 5 result (and never reports truncation)."""
    idx, data, queries = int_index
    cfg = taco_config(**CFG)  # auto cap: 4*beta*n = 320
    gi, _gd, gs = query_with_stats(idx, queries, cfg)
    truncated = np.asarray(gs["truncated"])
    assert truncated.any(), "fixture must exercise gather truncation"
    mi, md, ms = query_with_stats(
        idx, queries, dataclasses.replace(cfg, rerank="masked_full"))
    assert not np.asarray(ms["truncated"]).any()
    sc, _ = compute_sc_scores(idx, queries, cfg)
    sc = np.asarray(sc)
    beta_n = cfg.beta * data.shape[0]
    differs = 0
    for qi in range(queries.shape[0]):
        want_ids, want_d = _dynamic_alg5_oracle(
            sc[qi], data, queries[qi], beta_n, cfg.n_subspaces, cfg.k)
        np.testing.assert_array_equal(np.asarray(mi[qi]), want_ids)
        np.testing.assert_allclose(np.asarray(md[qi]), want_d, rtol=1e-6)
        differs += int(not np.array_equal(np.asarray(gi[qi]), want_ids))
    # at least one truncated query must actually have lost real neighbors,
    # otherwise this test isn't exercising the difference
    assert differs > 0


def test_fixed_selection_rides_masked_pipeline(int_index):
    """SuCo mode: same histogram-derived threshold as the rank-cut, demand
    includes threshold-level ties (>= budget), results stay exact."""
    idx, data, queries = int_index
    cfg = suco_config(**CFG, candidate_cap=4000)
    # reuse the TaCo-built index but query in fixed-selection mode
    cfg = dataclasses.replace(cfg, transform="entropy")
    gi, gd, gs = query_with_stats(idx, queries, cfg)
    mi, md, ms = query_with_stats(
        idx, queries, dataclasses.replace(cfg, rerank="masked_full"))
    np.testing.assert_array_equal(np.asarray(ms["sc_threshold"]),
                                  np.asarray(gs["sc_threshold"]))
    budget = fixed_budget(cfg.beta * data.shape[0], data.shape[0])
    assert (np.asarray(ms["candidate_demand"]) >= budget).all()
    # masked fixed mode re-ranks every tie at the threshold level, so its
    # top-k distances can only be <= the rank-cut gather path's
    md_np, gd_np = np.asarray(md), np.asarray(gd)
    assert (md_np <= gd_np + 1e-6).all()


def test_rerank_auto_resolution():
    cfg = taco_config(rerank="auto")
    assert resolve_rerank(cfg) == "masked_full"
    assert resolve_rerank(cfg, distributed=True) == "gather"
    with pytest.raises(ValueError):
        resolve_rerank(taco_config(rerank="bogus"))


def test_masked_serving_engine_override(int_index):
    """Per-request rerank override through the serving engine: identical
    results, truncated never set on the masked path."""
    from repro.serving import AnnRequest, AnnServingEngine

    idx, _data, queries = int_index
    cfg = taco_config(**CFG, candidate_cap=4000)
    engine = AnnServingEngine(idx, cfg, max_batch=8)
    res_g = engine.search([AnnRequest(query=q) for q in queries])
    res_m = engine.search(
        [AnnRequest(query=q, rerank="masked_full") for q in queries])
    for a, b in zip(res_g, res_m):
        np.testing.assert_array_equal(a.ids, b.ids)
        assert not b.truncated
    with pytest.raises(ValueError):
        engine.submit(AnnRequest(query=queries[0], rerank="bogus"))
