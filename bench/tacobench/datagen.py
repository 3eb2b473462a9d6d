"""Corpus and query pool on the device, from the run's seed.

A ``jax.random`` port of the repository's ``gmm_dataset`` / ``make_queries``
(``src/repro/data/vectors.py``), kept here so that a change to the program
cannot move the benchmark's data:

* cluster centres span a ``rank_frac * d`` subspace and have unit norm;
* each row is a centre plus ``cluster_std`` times power-law noise
  (variance of dimension ``i`` proportional to ``i**-noise_decay + 0.05``)
  under a random rotation;
* queries are fresh draws of the same mixture plus isotropic noise of
  ``query_noise`` times the corpus's standard deviation. The original holds
  out corpus rows as queries; a fresh draw has the same distribution and
  spares a copy of the corpus.

Probe queries. The first ``n_probes`` queries of the pool each get
``probe_group`` planted corpus rows at squared distances
``|q|^2 * (probe_radius + i * probe_spacing)``, ``i = 0 .. probe_group - 1``,
in random directions. The planted rows sit at evenly spaced positions over
the whole corpus (see :func:`probe_rows`), and the rows of one probe are
spread from its start to its end, so a served path that skips any part of
the corpus misses some probe's planted rows. The planted rows are far
closer to their query than any natural neighbour, so they collide with it
in every subspace and all reach the re-rank. Their squared distances are
spaced far wider than float32 rounding of a distance and far narrower than
bfloat16 rounding, so the order of the k served for a probe is fixed by
exact float32 re-ranking and scrambled by anything coarser.

Everything is made in one jitted call, in float32, chunk by chunk, so the
device holds the corpus and one chunk's temporaries at most.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding all 64 bits of ``seed``.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a Python int when
    64-bit mode is off, so seeds that differ above bit 31 would collide."""
    s = int(seed) % (1 << 64)
    words = np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _random_rotation(key, d: int) -> jax.Array:
    a = jax.random.normal(key, (d, d), jnp.float32)
    q, r = jnp.linalg.qr(a)
    return q * jnp.sign(jnp.diagonal(r))[None, :]


def chunk_rows(n: int, d: int, max_elems: int = 1 << 27) -> int:
    """Largest divisor of ``n`` whose chunk holds at most ``max_elems``
    floats (at least 1 row)."""
    best = 1
    for c in range(1, int(np.sqrt(n)) + 1):
        if n % c:
            continue
        for rows in (c, n // c):
            if rows * d <= max_elems and rows > best:
                best = rows
    return best


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_queries", "n_mix", "cluster_std", "rank_frac",
    "noise_decay", "query_noise", "n_probes", "probe_group", "probe_radius",
    "probe_spacing", "rows_per_chunk"))
def _make(key, *, n, d, n_queries, n_mix, cluster_std, rank_frac,
          noise_decay, query_noise, n_probes, probe_group, probe_radius,
          probe_spacing, rows_per_chunk):
    k_basis, k_centres, k_rot, k_rows, k_q, k_qnoise, k_probe = (
        jax.random.split(key, 7))
    r = max(2, int(rank_frac * d))
    basis = _random_rotation(k_basis, d)[:, :r]
    centres = jnp.matmul(jax.random.normal(k_centres, (n_mix, r)), basis.T,
                         precision=_HI)
    centres = centres / jnp.maximum(
        jnp.linalg.norm(centres, axis=1, keepdims=True), 1e-6)
    scales = jnp.arange(1, d + 1, dtype=jnp.float32) ** (-noise_decay) + 0.05
    scales = jnp.sqrt(scales / jnp.mean(scales))
    rot = _random_rotation(k_rot, d)

    def rows(k, m):
        k_which, k_noise = jax.random.split(k)
        which = jax.random.randint(k_which, (m,), 0, n_mix)
        noise = jax.random.normal(k_noise, (m, d), jnp.float32) * scales
        return centres[which] + cluster_std * jnp.matmul(noise, rot,
                                                         precision=_HI)

    n_chunks = n // rows_per_chunk

    def chunk(c):
        return rows(jax.random.fold_in(k_rows, c), rows_per_chunk)

    # the corpus's std, for the query noise, from a first pass that keeps
    # nothing but sums (the corpus is made once, in the second pass)
    def moments(c, acc):
        x = chunk(c)
        return acc + jnp.stack([jnp.mean(x), jnp.mean(x * x)])

    m1, m2 = jax.lax.fori_loop(0, n_chunks, moments,
                               jnp.zeros(2, jnp.float32)) / n_chunks
    std = jnp.sqrt(jnp.maximum(m2 - m1 * m1, 0.0))
    queries = rows(k_q, n_queries) + (query_noise * std) * jax.random.normal(
        k_qnoise, (n_queries, d), jnp.float32)
    if n_probes:
        q = queries[:n_probes]
        qn = jnp.sum(q * q, axis=1)
        steps = probe_radius + probe_spacing * jnp.arange(
            probe_group, dtype=jnp.float32)
        radius = jnp.sqrt(qn[:, None] * steps[None, :])  # (P, G)
        u = jax.random.normal(k_probe, (n_probes, probe_group, d), jnp.float32)
        u = u / jnp.linalg.norm(u, axis=2, keepdims=True)
        planted = (q[:, None, :] + radius[..., None] * u).reshape(-1, d)
    at = jnp.asarray(probe_rows(n, n_probes, probe_group))

    def fill(c, buf):  # in place: the device holds the corpus and one chunk
        x = chunk(c)
        if n_probes:  # this chunk's planted rows; the others are dropped
            local = at - c * rows_per_chunk
            local = jnp.where(local >= 0, local, rows_per_chunk)
            x = x.at[local].set(planted, mode="drop")
        return jax.lax.dynamic_update_slice(buf, x, (c * rows_per_chunk, 0))

    corpus = jax.lax.fori_loop(0, n_chunks, fill,
                               jnp.zeros((n, d), jnp.float32))
    return corpus, queries


def probe_rows(n: int, n_probes: int, probe_group: int) -> np.ndarray:
    """Corpus rows (P * G,) of the planted rows, probe-major: row
    ``p * G + g`` of the result holds planted row ``g`` of probe ``p``.
    Planted row ``g`` of probe ``p`` is corpus row ``(g * P + p) * stride``
    with ``stride = n // (P * G)``: the rows of one probe lie ``P * stride``
    apart, from the start of the corpus to its last ``n / G`` rows."""
    slots = n_probes * probe_group
    stride = n // max(1, slots)
    p, g = np.meshgrid(np.arange(n_probes), np.arange(probe_group),
                       indexing="ij")
    return ((g * n_probes + p) * stride).reshape(-1).astype(np.int32)


def make_data(seed: int, data_cfg: dict, *, rows_per_chunk: int | None = None):
    """``(corpus (n, d), queries (n_queries, d))`` float32 on the default
    device, from ``seed`` and the configuration's ``data`` block. The corpus
    is made ``rows_per_chunk`` rows at a time (a divisor of ``n``; by
    default the largest chunk of at most 2**27 floats)."""
    n, d = int(data_cfg["n"]), int(data_cfg["d"])
    n_probes = int(data_cfg.get("n_probes", 0))
    group = int(data_cfg.get("probe_group", 0))
    if n_probes * group >= n or n_probes > int(data_cfg["n_queries"]):
        raise ValueError("probes do not fit the corpus or the query pool")
    return _make(
        seed_key(seed), n=n, d=d, n_queries=int(data_cfg["n_queries"]),
        n_mix=int(data_cfg.get("n_mix", 64)),
        cluster_std=float(data_cfg.get("cluster_std", 0.15)),
        rank_frac=float(data_cfg.get("rank_frac", 0.4)),
        noise_decay=float(data_cfg.get("noise_decay", 1.0)),
        query_noise=float(data_cfg.get("query_noise", 0.01)),
        n_probes=n_probes, probe_group=group,
        probe_radius=float(data_cfg.get("probe_radius", 0.0)),
        probe_spacing=float(data_cfg.get("probe_spacing", 0.0)),
        rows_per_chunk=rows_per_chunk or chunk_rows(n, d))
