import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^^ must precede any jax import (same contract as dryrun.py).
#
# Dry-run for the paper's OWN technique at production scale: lower + compile
# the corpus-sharded TaCo query step and the distributed index-build steps
# (covariance / Lloyd / cell sizes) for a BILLION-point corpus on the
# single-pod (16x16) and multi-pod (2x16x16) meshes.
#
#   python -m repro.launch.dryrun_ann [--multi-pod] [--n 1e9] [--d 128]

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import taco_config
from repro.core.distributed import (
    index_pspecs,
    make_distributed_cell_sizes,
    make_distributed_cov,
    make_distributed_lloyd,
    make_distributed_query,
)
from repro.core.imi import IMISubspace, split_halves
from repro.core.taco import SCIndex
from repro.launch.mesh import dp_axes, make_production_mesh


def abstract_index(n: int, d: int, cfg, mesh, data_axes):
    """ShapeDtypeStruct SCIndex for an n-point corpus, sharded like prod."""
    from repro.core.transform import SubspaceTransform

    s = cfg.subspace_dim
    s1, s2 = split_halves(s)
    m = cfg.n_subspaces * s
    tr = SubspaceTransform(
        mean=jax.ShapeDtypeStruct((d,), jnp.float32),
        basis=jax.ShapeDtypeStruct((d, m), jnp.float32),
        eigvals=jax.ShapeDtypeStruct((m,), jnp.float32),
        n_subspaces=cfg.n_subspaces,
        subspace_dim=s,
    )
    subs = tuple(
        IMISubspace(
            centroids1=jax.ShapeDtypeStruct((cfg.sqrt_k, s1), jnp.float32),
            centroids2=jax.ShapeDtypeStruct((cfg.sqrt_k, s2), jnp.float32),
            assign1=jax.ShapeDtypeStruct((n,), jnp.int32),
            assign2=jax.ShapeDtypeStruct((n,), jnp.int32),
            cell_sizes=jax.ShapeDtypeStruct((cfg.sqrt_k, cfg.sqrt_k), jnp.int32),
        )
        for _ in range(cfg.n_subspaces)
    )
    idx = SCIndex(
        transform=tr, dim_perm=None, subspaces=subs,
        data=jax.ShapeDtypeStruct((n, d), jnp.float32),
        sub_dims=(s,) * cfg.n_subspaces,
        data_norms=jax.ShapeDtypeStruct((n,), jnp.float32),
    )
    specs = index_pspecs(idx, data_axes)
    return jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, sp))
        if sp is not None else l,
        idx, specs,
        is_leaf=lambda x: x is None,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n", type=float, default=1e9)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--rerank", choices=["gather", "masked_full", "auto"],
                    default="gather",
                    help="re-rank pipeline to lower/compile; 'auto' resolves "
                         "to gather for the corpus-sharded query (billion-"
                         "scale shards keep the gather path, see SCConfig)")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    # billion-scale: corpus sharded over ALL axes; query batch replicated
    da = (*dp_axes(args.multi_pod), "model")
    n_dev = 512 if args.multi_pod else 256
    n = int(args.n) // n_dev * n_dev  # even corpus shards
    cfg = taco_config(n_subspaces=6, subspace_dim=8, n_clusters=256 * 256,
                      alpha=0.01, beta=0.0005, k=50, candidate_cap=4096,
                      rerank=args.rerank)
    results = {"kind": "ann", "mesh": "2x16x16" if args.multi_pod else "16x16",
               "n": n, "d": args.d, "n_devices": n_dev,
               "rerank": args.rerank}

    idx_sds = abstract_index(n, args.d, cfg, mesh, da)
    q_sds = jax.ShapeDtypeStruct(
        (args.queries, args.d), jnp.float32,
        sharding=NamedSharding(mesh, P(None, None)),
    )
    from repro.launch.hlo_analysis import analyze

    with jax.set_mesh(mesh):
        jobs = {
            "query": lambda: make_distributed_query(mesh, cfg, idx_sds, n, da, query_axes=())
            .lower(idx_sds, q_sds),
            "build_cov": lambda: jax.jit(
                make_distributed_cov(mesh, n, da).__wrapped__
            ).lower(jax.ShapeDtypeStruct((n, args.d), jnp.float32,
                                         sharding=NamedSharding(mesh, P(da, None)))),
            "build_lloyd": lambda: jax.jit(
                make_distributed_lloyd(mesh, da).__wrapped__
            ).lower(
                jax.ShapeDtypeStruct((n, 4), jnp.float32,
                                     sharding=NamedSharding(mesh, P(da, None))),
                jax.ShapeDtypeStruct((cfg.sqrt_k, 4), jnp.float32,
                                     sharding=NamedSharding(mesh, P())),
            ),
        }
        for name, lower in jobs.items():
            t0 = time.perf_counter()
            lowered = lower()
            compiled = lowered.compile()
            h = analyze(compiled.as_text())
            mem = {}
            try:
                ma = compiled.memory_analysis()
                mem = {k: int(getattr(ma, k)) for k in
                       ("argument_size_in_bytes", "temp_size_in_bytes")
                       if hasattr(ma, k)}
            except Exception:
                pass
            results[name] = {
                "compile_s": round(time.perf_counter() - t0, 2),
                "flops": h["flops"], "bytes": h["bytes"],
                "collective_total": h["collective_total"],
                "memory_analysis": mem,
            }
            print(f"[ann/{name}] ok compile={results[name]['compile_s']}s "
                  f"flops={h['flops']:.3e} bytes={h['bytes']:.3e} "
                  f"coll={h['collective_total']:.3e} mem={mem}", flush=True)

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = f"ann_taco__n{n}__{results['mesh'].replace('x', '_')}"
        if args.rerank != "gather":
            tag += f"__{args.rerank}"
        with open(os.path.join(args.out, f"{tag}.json"), "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
