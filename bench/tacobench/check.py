"""The comparison that decides ``correct``, and recall@10.

Every request a run sent is judged against the plain reference
(:mod:`tacobench.reference`) once the window has closed:

* ``bad_answers`` (exact, limit 0): requests with no answer by the grace
  deadline, an error, or an answer that says something wrong about itself:
  a wrong shape, an id outside the corpus, an id twice, a distance that is
  not finite or is not the squared distance of its id. Both sides compute
  that distance as ``sum((x - q)^2)`` in float32 over the same numbers and
  differ only in summation order, at most ``d * 2**-24`` of it (6e-5 at
  d = 960); ``DIST_RTOL`` allows sixteen times that.
* ``probe_mismatch``: the share of the probe requests (queries with
  planted neighbours, see :mod:`tacobench.datagen`) whose first
  ``min(k, PROBE_SLOTS)`` served ids are not the reference's, slot by
  slot.
* ``recall_at_10``: mean over the window's answered non-probe requests
  with ``k >= 10`` of the share of the reference's 10 nearest among the
  first 10 served. It is an end-to-end metric and a check: it may not fall
  below the configuration's floor (``limits.recall_at_10_min``). The
  probes' planted rows collide in every subspace, so only this number sees
  a fault in pass 1 (the collision inputs, ``schist``, the threshold) that
  drops natural neighbours.

Each check in ``checks`` is ``{"value": v, "max": limit}`` or
``{"value": v, "min": limit}``; a value of ``None`` (nothing to compare)
fails.
"""
from __future__ import annotations

import numpy as np

DIST_RTOL = 1e-3
PROBE_SLOTS = 10
RECALL_K = 10


def _served(req, n: int):
    """``(ids, dists)`` of a well-formed answer, else ``None``."""
    res = req.result
    if req.error is not None or res is None:
        return None
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    if ids.shape != (req.k,) or dists.shape != (req.k,):
        return None
    if ids.min() < 0 or ids.max() >= n or len(np.unique(ids)) != req.k:
        return None
    if not np.all(np.isfinite(dists)):
        return None
    return ids.astype(np.int64), dists.astype(np.float32)


def compare(requests, window_requests, pool: np.ndarray, corpus, n_probes: int,
            limits: dict, *, knn, dists_of) -> dict:
    """Judge ``requests``; recall over ``window_requests`` (a subset).

    ``knn(queries, k) -> (ids, dists)`` is the reference, and
    ``dists_of(queries, ids) -> dists`` its exact distances of given ids."""
    n = corpus.shape[0]
    served = {id(r): _served(r, n) for r in requests}
    ok = [r for r in requests if served[id(r)] is not None]
    bad = len(requests) - len(ok)
    out = {"checked": len(requests), "recall_at_10": None}
    if ok:
        # the served distances must be the distances of the served ids
        kmax = max(r.k for r in ok)
        ids = np.zeros((len(ok), kmax), np.int64)
        for row, r in enumerate(ok):
            ids[row, :r.k] = served[id(r)][0]
            ids[row, r.k:] = served[id(r)][0][-1]
        exact = dists_of(pool[[r.pool_index for r in ok]], ids)
        good = []
        for row, r in enumerate(ok):
            got = served[id(r)][1]
            want = exact[row, :r.k]
            if np.all(np.abs(got - want) <= DIST_RTOL * np.maximum(want, 1e-12)):
                good.append(r)
        bad += len(ok) - len(good)
        ok = good
    out["bad_answers"] = bad
    if ok:
        distinct = sorted({r.pool_index for r in ok})
        ref_ids, _ = knn(pool[distinct], max(RECALL_K, max(r.k for r in ok)))
        ref = {p: ref_ids[i] for i, p in enumerate(distinct)}
        probes = [r for r in ok if r.pool_index < n_probes]
        wrong = sum(
            1 for r in probes
            if not np.array_equal(served[id(r)][0][:min(r.k, PROBE_SLOTS)],
                                  ref[r.pool_index][:min(r.k, PROBE_SLOTS)]))
        out["probe_mismatch"] = wrong / len(probes) if probes else None
        in_window = {id(r) for r in window_requests}
        recalls = [
            len(set(served[id(r)][0][:RECALL_K]) & set(ref[r.pool_index][:RECALL_K]))
            / RECALL_K
            for r in ok
            if id(r) in in_window and r.pool_index >= n_probes and r.k >= RECALL_K
        ]
        out["recall_at_10"] = float(np.mean(recalls)) if recalls else None
    out["checks"] = {
        "bad_answers": {"value": bad, "max": 0},
        "probe_mismatch": {"value": out.get("probe_mismatch"),
                           "max": limits["probe_mismatch"]},
        "recall_at_10": {"value": out["recall_at_10"],
                         "min": limits["recall_at_10_min"]},
    }
    out["correct"] = bool(len(requests) > 0 and all(
        passes(c) for c in out["checks"].values()))
    return out


def passes(check: dict) -> bool:
    """Whether one entry of ``checks`` is within its limit."""
    v = check["value"]
    if v is None:
        return False
    return v <= check["max"] if "max" in check else v >= check["min"]
