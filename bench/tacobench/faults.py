"""Faults planted in pass 1 of the served path, where only the recall floor
of ``correct`` can see them: the probes' planted rows collide in every
subspace, so they reach the re-rank whatever pass 1 does to the others.

Used to read the upper end of the recall floor (``bench/calibrate.py
--fault``) and by ``bench/tests/test_correct.py``; a benchmark run plants
none.

* ``beta_halved``: the threshold (Alg. 5) is read off the histogram with
  half the configured candidate budget ``beta * n``.
* ``threshold_raised``: the threshold is one collision level higher than
  Alg. 5 gives, so the lowest admitted level is left out.
* ``subspace_zeroed``: the collision inputs give every point cell 0 in the
  first subspace, as if its codes had been zeroed.
"""
from __future__ import annotations

import contextlib

FAULTS = ("beta_halved", "threshold_raised", "subspace_zeroed")


@contextlib.contextmanager
def planted(name: str | None):
    """Plant fault ``name`` (``None``: none) in ``repro.core.taco`` for the
    duration; JAX's traces are dropped on entry and exit, so programs traced
    inside see the fault and programs traced outside do not."""
    if name is None:
        yield
        return
    import jax

    from repro.core import taco

    if name in ("beta_halved", "threshold_raised"):
        attr = "query_aware_threshold"
        orig = taco.query_aware_threshold

        def broken(hist, beta_n, n_subspaces):
            if name == "beta_halved":
                return orig(hist, 0.5 * beta_n, n_subspaces)
            last, count = orig(hist, beta_n, n_subspaces)
            return last + 1, count
    elif name == "subspace_zeroed":
        attr = "_collision_inputs"
        orig = taco._collision_inputs

        def broken(*args, **kwargs):
            d1s, d2s, a1s, a2s, taus, retrieved = orig(*args, **kwargs)
            return (d1s, d2s, a1s.at[0].set(0), a2s.at[0].set(0), taus,
                    retrieved)
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    setattr(taco, attr, broken)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(taco, attr, orig)
        jax.clear_caches()
