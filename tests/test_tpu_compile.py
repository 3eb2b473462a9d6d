"""The served query path compiles for a TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e topology. That catches what interpret mode cannot —
block shapes off the (8, 128) tiling, ops Mosaic cannot lower, programs
that do not fit — at real widths (Q = 64, n = 2^20, d = 128).

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
tests steer kernel selection themselves: ``ops._on_tpu`` is patched to
True, which makes ``impl="auto"`` and ``impl="pallas"`` pick the compiled
(not interpreted) Pallas kernels while tracing.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

Q, N, D = 64, 1 << 20, 128
N_SUB, SQRT_K = 6, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_kernels(monkeypatch):
    """Trace as if on a TPU: ops pick the compiled Pallas kernels."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    # a persistent-cache entry for the described chip could not be read
    # back here; keep these compiles out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _collision_inputs(one_chip):
    return (
        _sds(one_chip, (N_SUB, Q, SQRT_K), jnp.float32),
        _sds(one_chip, (N_SUB, Q, SQRT_K), jnp.float32),
        _sds(one_chip, (N_SUB, N), jnp.int32),
        _sds(one_chip, (N_SUB, N), jnp.int32),
        _sds(one_chip, (N_SUB, Q), jnp.float32),
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_schist_compiles(one_chip, tpu_kernels):
    compiled = _compile(lambda *a: ops.schist(*a, impl="pallas"),
                        *_collision_inputs(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_scscore_compiles(one_chip, tpu_kernels):
    """The gather path's kernel (``use_kernels=True``) tiles the same way."""
    compiled = _compile(lambda *a: ops.scscore(*a, impl="pallas"),
                        *_collision_inputs(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("precision,k", [
    pytest.param("f32", 10, id="f32"),
    pytest.param("bf16", 10, id="bf16"),
    # k 100: the skip rule reads lane 99 of the (bq, 128) state
    pytest.param("f32", 100, id="f32-k100"),
    pytest.param("bf16", 100, id="bf16-k100"),
])
def test_masked_rerank_compiles(one_chip, tpu_kernels, precision, k):
    """With the skip rule's scalar branch (a full vector reduction) and the
    merge count, as the query executable runs it."""
    args = _collision_inputs(one_chip) + (
        _sds(one_chip, (Q,), jnp.int32),
        _sds(one_chip, (N, D), jnp.float32),
        _sds(one_chip, (N,), jnp.float32),
        _sds(one_chip, (Q, D), jnp.float32),
    )
    compiled = _compile(
        lambda *a: ops.masked_rerank(*a, k, impl="pallas",
                                     precision=precision, counts=True),
        *args)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the benchmark's roofline reader finds pass 2 by this name
    assert "%masked_rerank_pallas" in text


def test_l2dist_compiles(one_chip, tpu_kernels):
    compiled = _compile(lambda x, y: ops.l2dist(x, y, impl="pallas"),
                        _sds(one_chip, (Q, D), jnp.float32),
                        _sds(one_chip, (N, D), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def abstract_index(one_chip):
    """ShapeDtypeStructs of an N-point index: a small real build with every
    corpus-length leaf stretched to N rows."""
    from repro.core import build, taco_config
    from repro.data import gmm_dataset

    n_small = 3000
    cfg = taco_config(n_subspaces=N_SUB, subspace_dim=8,
                      n_clusters=SQRT_K * SQRT_K, alpha=0.05, beta=0.02, k=10)
    small = build(gmm_dataset(n_small, D, seed=0), cfg)

    def stretch(x):
        shape = (N,) + x.shape[1:] if x.shape[:1] == (n_small,) else x.shape
        return _sds(one_chip, shape, x.dtype)

    return jax.tree.map(stretch, small), cfg


@pytest.mark.parametrize("rerank", ["masked_full", "gather"])
def test_query_executable_takes_corpus_as_argument(
        one_chip, tpu_kernels, abstract_index, rerank):
    """The single-device query executable takes the index as an argument
    (no corpus constant in the program) and, on masked_full, runs the
    Pallas kernels."""
    from repro.ann.searcher import single_device_query

    index, cfg = abstract_index
    cfg = dataclasses.replace(cfg, rerank=rerank)
    queries = _sds(one_chip, (Q, D), jnp.float32)
    compiled = single_device_query.lower(index, queries, cfg=cfg,
                                         k=10).compile()
    corpus_bytes = N * D * np.dtype(np.float32).itemsize
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= corpus_bytes
    assert ("tpu_custom_call" in compiled.as_text()) == (rerank == "masked_full")
