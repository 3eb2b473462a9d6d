#!/usr/bin/env python3
"""Smoke run of TaCo's served query path on a TPU, through the user entry
points: ``AnnIndex.build`` -> ``index.engine(...)`` -> ``engine.search``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: corpus-sharded serving only

One chip: a 1,000,000 x 128 f32 corpus at the shape of ANN-Benchmarks'
``sift-128-euclidean`` (SIFT1M), generated from ``--seed`` by
``repro.data.gmm_dataset`` with 64 held-out queries (``make_queries``),
k = 10, and the TaCo config ``serve_ann`` builds. Phases:

  (a) build the index;
  (b) serve waves of requests (buckets 16 and 64, some with a per-request
      k) through the single-device engine on the masked_full pipeline,
      check that its executable holds the Pallas kernels, and that its ids
      agree with the jnp twins of both passes;
  (c) serve the same waves on the default gather pipeline;
  (d) check recall@10 of (b) and (c) against a float64 numpy exact k-NN on
      the host.

Four chips: a 4,000,000 x 128 corpus served by the sharded engine over all
four chips on masked_full, compared slot by slot with the single-device
engine on chip 0 and with the host exact k-NN.

A missing TPU is an error (exit 2), not a fallback. Any failed check exits
1. Only a run in which every check passed prints, as its last line,
``{"ok": true, "device": {"platform", "kind", "count"}}``. The JAX compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in
the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

N_ONE_CHIP = 1_000_000  # SIFT1M rows
N_FOUR_CHIPS = 4_000_000
DIM = 128
K = 10
N_QUERIES = 64
#: k of the per-request override rows (served at bucket 16)
K_OVERRIDE = 5
#: share of (query, slot) pairs that must agree between two pipelines
MIN_AGREEMENT = 0.99
#: recall@10 floor for both pipelines (and for --chips 4, where recall at
#: 4M rows is expected to be no lower: it rose with n in CPU runs). It is
#: the recall the same phases, config, seed and size gave on a CPU (f32,
#: jnp twins, a TPU host's CPU with JAX_PLATFORMS=cpu): 0.9359375 for both
#: masked_full and gather, less 0.02.
RECALL_FLOOR = 0.9159375


def taco_cfg(**kw):
    """The TaCo config ``repro.launch.serve_ann`` builds."""
    from repro.core import taco_config

    base = dict(n_subspaces=6, subspace_dim=8, n_clusters=1024, alpha=0.05,
                beta=0.02, k=K)
    return taco_config(**{**base, **kw})


def make_data(n: int, d: int, n_queries: int, seed: int):
    """(corpus (n, d), queries (n_queries, d)) float32 from ``seed``."""
    from repro.data import gmm_dataset, make_queries

    return make_queries(gmm_dataset(n + n_queries, d, seed=seed), n_queries,
                        seed=seed + 1)


def build(corpus, cfg):
    """Phase (a): ``(AnnIndex, build seconds)``."""
    import jax

    from repro.ann import AnnIndex

    t0 = time.perf_counter()
    index = AnnIndex.build(corpus, cfg)
    jax.block_until_ready(index.sc_index)
    return index, time.perf_counter() - t0


def waves(n_queries: int):
    """Request waves as lists of (query row, k or None): 16 rows at the
    default k (bucket 16); then the other 48 at the default k (bucket 64)
    together with 12 repeats at ``K_OVERRIDE`` (bucket 16)."""
    assert n_queries >= 64, n_queries
    first = [(r, None) for r in range(16)]
    second = [(r, None) for r in range(16, 64)]
    second += [(r, K_OVERRIDE) for r in range(12)]
    return [first, second]


def serve(engine, queries, request_waves):
    """Serve each wave through ``engine.search``. Returns ``(ids (64, k) of
    the default-k rows, {row: ids} of the override rows)``."""
    from repro.serving import AnnRequest

    full, override = {}, {}
    for wave in request_waves:
        results = engine.search(
            [AnnRequest(query=queries[r], k=k) for r, k in wave])
        for (r, k), res in zip(wave, results):
            (full if k is None else override)[r] = np.asarray(res.ids)
    return np.stack([full[r] for r in sorted(full)]), override


def prefix_agreement(full_ids, override):
    """Share of override slots equal to the same query's default-k slots:
    a smaller k must return a prefix of the larger one."""
    same = total = 0
    for r, ids in override.items():
        same += int(np.sum(ids == full_ids[r, : ids.shape[0]]))
        total += ids.shape[0]
    return same / total


def twin_ids(sc_index, queries, cfg, k: int):
    """ids of the masked_full pipeline with both passes forced onto the
    jnp twins (``impl="jnp"``), from the same collision inputs the served
    executable computes."""
    import jax
    import jax.numpy as jnp

    from repro.core.selection import query_aware_threshold
    from repro.core.taco import _collision_inputs, data_norms_of
    from repro.kernels import ops

    @jax.jit
    def run(index, q):
        d1s, d2s, a1s, a2s, taus, _ = _collision_inputs(index, q, cfg)
        hist = ops.schist(d1s, d2s, a1s, a2s, taus, impl="jnp")
        thresh, _ = query_aware_threshold(hist, float(cfg.beta * index.n),
                                          cfg.n_subspaces)
        ids, _ = ops.masked_rerank(
            d1s, d2s, a1s, a2s, taus, thresh, index.data,
            data_norms_of(index), q, k, impl="jnp", precision=cfg.precision)
        return ids

    return np.asarray(run(sc_index, jnp.asarray(queries)))


def executable_text(sc_index, cfg, bucket: int, k: int) -> str:
    """Compiled text of the single-device query executable for one key."""
    import jax
    import jax.numpy as jnp

    from repro.ann.searcher import single_device_query

    q = jax.ShapeDtypeStruct((bucket, sc_index.data.shape[1]), jnp.float32)
    return single_device_query.lower(sc_index, q, cfg=cfg, k=k).compile().as_text()


def exact_knn_host(corpus, queries, k: int) -> np.ndarray:
    """Exact k-NN ids by float64 numpy on the host (independent of JAX)."""
    x = np.asarray(corpus, np.float64)
    q = np.asarray(queries, np.float64)
    d = (np.sum(q * q, axis=1)[:, None] - 2.0 * (q @ x.T)
         + np.sum(x * x, axis=1)[None, :])
    part = np.argpartition(d, k, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, part, axis=1), axis=1,
                       kind="stable")
    return np.take_along_axis(part, order, axis=1)


def recall_at_k(ids, gt, k: int) -> float:
    return float(np.mean([len(set(a[:k]) & set(b[:k])) / k
                          for a, b in zip(ids, gt)]))


def agreement(a, b) -> float:
    """Share of (query, slot) pairs with equal ids."""
    return float(np.mean(np.asarray(a) == np.asarray(b)))


def phase_masked(index, queries, cfg):
    """Phase (b): masked_full through the single-device engine."""
    engine = index.engine("single", max_batch=64, cfg=cfg)
    ids, override = serve(engine, queries, waves(len(queries)))
    return {
        "ids": ids,
        "compiles": sum(engine.compile_counts.values()),
        "prefix_agreement": prefix_agreement(ids, override),
        "has_kernels": "tpu_custom_call" in executable_text(
            index.sc_index, cfg, 64, K),
        "twin_agreement": agreement(
            ids, twin_ids(index.sc_index, queries, cfg, K)),
    }


def phase_gather(index, queries, cfg):
    """Phase (c): the default gather pipeline, same requests."""
    engine = index.engine("single", max_batch=64, cfg=cfg)
    ids, override = serve(engine, queries, waves(len(queries)))
    return {
        "ids": ids,
        "compiles": sum(engine.compile_counts.values()),
        "prefix_agreement": prefix_agreement(ids, override),
    }


def phase_sharded(index, queries, cfg, shards: int):
    """Four-chip phase: the sharded engine against the single-device one."""
    import jax

    sharded = index.engine("sharded", shards=shards, max_batch=64, cfg=cfg)
    devices = {d for leaf in jax.tree.leaves(sharded.searcher.placed_index)
               for d in leaf.devices()}
    ids, _ = serve(sharded, queries, waves(len(queries))[1:])
    single = index.engine("single", max_batch=64, cfg=cfg)
    ids_single, _ = serve(single, queries, waves(len(queries))[1:])
    return {
        "ids": ids,
        "ids_single": ids_single,
        "placed_devices": len(devices),
        "compiles": sum(sharded.compile_counts.values()),
    }


class Checks:
    """Prints each check and remembers whether all passed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str):
        print(f"check {name}: {detail} -> {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)


def run_one_chip(seed: int, check: Checks):
    corpus, queries = make_data(N_ONE_CHIP, DIM, N_QUERIES, seed)
    cfg = taco_cfg()
    index, secs = build(corpus, cfg)
    print(f"build: n={index.n} d={index.d} {secs:.3f} s", flush=True)
    gt = exact_knn_host(corpus, queries, K)

    t0 = time.perf_counter()
    masked = phase_masked(index, queries, dataclasses.replace(
        cfg, rerank="masked_full"))
    print(f"masked_full: compiles={masked['compiles']} phase "
          f"{time.perf_counter() - t0:.3f} s (compiles included)", flush=True)
    check("masked_full executable holds Pallas kernels",
          masked["has_kernels"], f"tpu_custom_call={masked['has_kernels']}")
    pairs = masked["ids"].size
    check("masked_full ids agree with the jnp twins",
          masked["twin_agreement"] >= MIN_AGREEMENT,
          f"{round(masked['twin_agreement'] * pairs)}/{pairs} (query, slot) "
          f"pairs = {masked['twin_agreement']:.6f}, need >= {MIN_AGREEMENT}")

    t0 = time.perf_counter()
    gather = phase_gather(index, queries, cfg)
    print(f"gather: compiles={gather['compiles']} phase "
          f"{time.perf_counter() - t0:.3f} s (compiles included)", flush=True)
    for name, res in (("masked_full", masked), ("gather", gather)):
        check(f"{name} k={K_OVERRIDE} overrides are prefixes of k={K}",
              res["prefix_agreement"] >= MIN_AGREEMENT,
              f"{res['prefix_agreement']:.6f}, need >= {MIN_AGREEMENT}")
        rec = recall_at_k(res["ids"], gt, K)
        check(f"{name} recall@{K} vs host exact", rec >= RECALL_FLOOR,
              f"{rec:.6f}, floor {RECALL_FLOOR}")


def run_four_chips(seed: int, check: Checks):
    corpus, queries = make_data(N_FOUR_CHIPS, DIM, N_QUERIES, seed)
    cfg = taco_cfg(rerank="masked_full")
    index, secs = build(corpus, cfg)
    print(f"build: n={index.n} d={index.d} {secs:.3f} s", flush=True)
    gt = exact_knn_host(corpus, queries, K)
    t0 = time.perf_counter()
    res = phase_sharded(index, queries, cfg, 4)
    print(f"sharded: compiles={res['compiles']} phase "
          f"{time.perf_counter() - t0:.3f} s (compiles included)", flush=True)
    check("index leaves placed across all chips", res["placed_devices"] == 4,
          f"{res['placed_devices']} devices")
    agree = agreement(res["ids"], res["ids_single"])
    check("sharded ids agree with single-device ids",
          agree >= MIN_AGREEMENT,
          f"{round(agree * res['ids'].size)}/{res['ids'].size} (query, slot) "
          f"pairs = {agree:.6f}, need >= {MIN_AGREEMENT}")
    for name, ids in (("sharded", res["ids"]), ("single", res["ids_single"])):
        rec = recall_at_k(ids, gt[16:64], K)
        check(f"{name} recall@{K} vs host exact", rec >= RECALL_FLOOR,
              f"{rec:.6f}, floor {RECALL_FLOOR}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_checkout_cache

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device}", flush=True)
    print(f"compile cache: {use_checkout_cache()}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        check("four TPU devices", len(devices) == 4 and all(
            d.platform == "tpu" for d in devices), f"{devices}")
        run_four_chips(args.seed, check)
    else:
        run_one_chip(args.seed, check)
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use (chip 0): {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(f"wall: {time.perf_counter() - t0:.3f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
