import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax-importing module: jax locks device count on init.
#
# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
# ShapeDtypeStruct inputs (no allocation) and record memory / cost /
# collective analysis to a JSON artifact for benchmarks/roofline.py.
#
# Usage:
#   python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k \
#       [--multi-pod] [--out benchmarks/artifacts]
#   python -m repro.launch.dryrun --all [--multi-pod]   # full sweep

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, input_specs, skip_reason
from repro.configs.shapes import resolve_arch_for_shape
from repro.launch import sharding as SH
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.model import decode_step, forward, init_params, prefill
from repro.optim import adafactor, adamw
from repro.train.loop import TrainState, make_train_step
from repro.optim.schedule import warmup_cosine

_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|u32|s8|u8|pred|s64|u64)\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _mem_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float)) and (k.startswith("bytes") or k in ("flops", "transcendentals") or "utilization" not in k)}


def pick_optimizer(arch):
    n_approx = arch.n_layers * arch.d_model * arch.d_ff * (
        3 * max(arch.n_experts, 1)
    )
    return (adafactor(), "adafactor") if n_approx > 1e11 else (adamw(), "adamw")


def sharded_arch(arch, multi_pod: bool, dp_shards: int | None = None):
    dp = dp_axes(multi_pod)
    if dp_shards is None:
        dp_shards = 32 if multi_pod else 16
    # MoE buffer (E, chunks, cap, D): experts over 'model' when the count
    # divides, else per-expert TP on D (granite-moe: 40 % 16 != 0); token
    # chunks over DP (shard-local dispatch, see moe_apply docstring).
    ep = (
        P("model", dp, None, None)
        if arch.n_experts and arch.n_experts % 16 == 0
        else P(None, dp, None, "model")
    )
    return dataclasses.replace(
        arch,
        ep_spec=ep,
        act_spec=P(dp, None, None),
        moe_dispatch_chunks=dp_shards if arch.n_experts else 1,
        moe_impl="manual" if arch.n_experts and arch.n_experts % 16 == 0 else "gspmd",
    )


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool):
    """Returns (lowered, compiled, meta) for one cell."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(multi_pod)
    shape = SHAPES[shape_name]
    arch = get_arch(arch_name)
    reason = skip_reason(arch, shape)
    if reason:
        return None, None, {"skipped": reason}
    arch = resolve_arch_for_shape(arch, shape)
    arch = sharded_arch(arch, multi_pod)
    if shape.kind in ("decode", "prefill"):
        # inference serves bf16 weights (halves the param-read term that
        # dominates decode; §Perf llava long_500k iteration)
        arch = dataclasses.replace(arch, param_dtype="bfloat16")

    specs = input_specs(arch, shape)
    params_shape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), arch))
    pspecs = SH.sanitize_specs(params_shape, SH.param_pspecs(params_shape), mesh)
    params_sharded = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        params_shape, pspecs,
    )
    meta = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "param_count": int(sum(x.size for x in jax.tree_util.tree_leaves(params_shape))),
        "attention_kind": arch.attention_kind,
    }

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt, opt_name = pick_optimizer(arch)
            meta["optimizer"] = opt_name
            meta["step_kind"] = "train_step"
            opt_shape = jax.eval_shape(opt[0], params_shape)
            state_shape = TrainState(params=params_shape, opt_state=opt_shape,
                                     step=jax.ShapeDtypeStruct((), jnp.int32))
            state_specs = SH.sanitize_specs(
                state_shape, SH.train_state_pspecs(state_shape, dp, mesh), mesh
            )
            state_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                state_shape, state_specs,
            )
            batch_specs = SH.sanitize_specs(specs, SH.batch_pspecs(specs, dp), mesh)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                specs, batch_specs,
            )
            step = make_train_step(
                arch, opt, warmup_cosine(3e-4, 100, 10000), jit_compile=False
            )
            lowered = jax.jit(step, donate_argnums=(0,)).lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            meta["step_kind"] = "prefill"
            batch_specs = SH.sanitize_specs(specs, SH.batch_pspecs(specs, dp), mesh)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                specs, batch_specs,
            )

            def prefill_fn(params, batch):
                return prefill(params, arch, batch, shape.seq_len)

            lowered = jax.jit(prefill_fn).lower(params_sharded, batch_sds)
        else:  # decode
            meta["step_kind"] = "serve_step"
            cache_shape = specs["cache"]
            cache_specs = SH.sanitize_specs(
                cache_shape, SH.cache_pspecs(cache_shape, dp), mesh
            )
            cache_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                cache_shape, cache_specs,
            )
            tok_spec = SH.sanitize_specs(specs["tokens"], P(dp, None), mesh)
            tok_sds = jax.ShapeDtypeStruct(
                specs["tokens"].shape, jnp.int32, sharding=NamedSharding(mesh, tok_spec)
            )
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))

            def serve_step(params, cache, tokens, pos):
                return decode_step(params, arch, cache, tokens, pos)

            lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
                params_sharded, cache_sds, tok_sds, pos_sds
            )
    return lowered, mesh, meta


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    t0 = time.perf_counter()
    try:
        lowered, mesh, meta = lower_cell(arch_name, shape_name, multi_pod)
    except Exception as e:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "error": f"lower: {type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}
    if lowered is None:
        return meta | {"arch": arch_name, "shape": shape_name}
    meta["lower_s"] = round(time.perf_counter() - t0, 2)
    t1 = time.perf_counter()
    try:
        compiled = lowered.compile()
    except Exception as e:
        return meta | {"error": f"compile: {type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
    meta["compile_s"] = round(time.perf_counter() - t1, 2)
    mem = _mem_analysis(compiled)
    cost = _cost_analysis(compiled)
    print(f"[{meta['arch']} x {meta['shape']} x {meta['mesh']}] memory_analysis:", mem)
    print(f"[{meta['arch']} x {meta['shape']} x {meta['mesh']}] cost_analysis:",
          {k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    meta["memory_analysis"] = mem
    meta["cost_analysis"] = cost
    from repro.launch.hlo_analysis import analyze

    meta["hlo_analysis"] = analyze(hlo)
    meta["collectives"] = {
        **meta["hlo_analysis"]["collective_bytes"],
        "counts": meta["hlo_analysis"]["collective_counts"],
        "total": meta["hlo_analysis"]["collective_total"],
    }
    meta["hlo_kb"] = len(hlo) // 1024
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch_name}__{shape_name}__{meta['mesh'].replace('x','_')}"
        with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
            json.dump(meta, f, indent=1)
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    cells = (
        [(a, s) for a in ARCHS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = 0
    for arch_name, shape_name in cells:
        r = run_cell(arch_name, shape_name, args.multi_pod, args.out)
        status = ("SKIP: " + r["skipped"][:60]) if "skipped" in r else (
            "FAIL: " + r["error"][:120] if "error" in r else
            f"ok lower={r['lower_s']}s compile={r['compile_s']}s "
            f"flops={r['hlo_analysis']['flops']:.3e} "
            f"coll={r['collectives']['total']:.3e}B"
        )
        print(f"{arch_name:24s} {shape_name:12s} {r.get('mesh','')}  {status}", flush=True)
        failures += 1 if "error" in r else 0
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
