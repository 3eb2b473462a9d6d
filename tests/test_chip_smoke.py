"""``chip_smoke.py`` on the CPU: its phases run at a tiny size (Pallas
kernels interpreted), and its ``main()`` refuses to run without a TPU."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.kernels import ops
from repro.utils import exact_knn

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny(cs):
    corpus, queries = cs.make_data(4000, 64, cs.N_QUERIES, seed=0)
    cfg = cs.taco_cfg(n_clusters=256)
    index, secs = cs.build(corpus, cfg)
    assert secs > 0
    return cs, corpus, queries, cfg, index


def test_exact_host_reference_matches_brute_force(tiny):
    cs, corpus, queries, _cfg, _index = tiny
    _d, want = exact_knn(corpus, queries, cs.K)
    np.testing.assert_array_equal(cs.exact_knn_host(corpus, queries, cs.K),
                                  want)


def test_phases_run_at_tiny_size(tiny, monkeypatch):
    cs, corpus, queries, cfg, index = tiny
    # run the Pallas kernels (interpreted) where a TPU would run them
    real = ops._resolve
    monkeypatch.setattr(
        ops, "_resolve",
        lambda impl: (True, True) if impl == "auto" else real(impl))
    gt = cs.exact_knn_host(corpus, queries, cs.K)
    masked = cs.phase_masked(index, queries,
                             dataclasses.replace(cfg, rerank="masked_full"))
    gather = cs.phase_gather(index, queries, cfg)
    # three (bucket, k) keys each: (16, 10), (64, 10), (16, 5)
    assert masked["compiles"] == gather["compiles"] == 3
    assert masked["twin_agreement"] >= cs.MIN_AGREEMENT
    assert not masked["has_kernels"]  # interpreted kernels are no custom call
    for res in (masked, gather):
        assert res["ids"].shape == (cs.N_QUERIES, cs.K)
        assert res["prefix_agreement"] == 1.0
    assert cs.agreement(masked["ids"], gather["ids"]) >= cs.MIN_AGREEMENT
    assert cs.recall_at_k(masked["ids"], gt, cs.K) > 0.5


def test_main_refuses_cpu(cs, capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "cpu" in out.err
