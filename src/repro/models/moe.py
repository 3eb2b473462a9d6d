"""Mixture-of-Experts FFN — top-k routing with capacity-based dispatch
(Switch/Mixtral style), expert-parallel friendly.

Dispatch is the scatter-to-buffer formulation: tokens are placed into an
(E, C, D) expert buffer at their position-in-expert (prefix-sum of the
routing one-hot); tokens beyond capacity C are dropped (standard dropped-
token MoE). Expert FFNs run as batched einsums over the expert axis, which
shards cleanly over the mesh's model axis (EP); the token->buffer scatter
becomes the all-to-all under GSPMD.

Returns the load-balancing auxiliary loss (Switch eq. 4) alongside outputs.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.sharding_utils import constrain


def moe_apply_manual(
    p,
    x: jax.Array,  # (B, S, D) — global, batch sharded over dp_axes
    *,
    n_experts: int,
    experts_per_token: int,
    capacity_factor: float = 1.25,
    dp_axes=("data",),
    ep_axis: str = "model",
):
    """Explicit shard_map MoE — the §Perf fix for the collective-bound cells.

    GSPMD's scatter/gather partitioners replicate the (kT, D) dispatch
    intermediates regardless of constraints (arctic iteration 2). This
    variant makes the sharding manual: every device routes its LOCAL tokens,
    dispatches only to its LOCAL experts (weights are expert-sharded over
    `ep_axis`), computes, and the partial combine is one bf16 psum of the
    (T_local, D) output over the expert axis. Per-layer comm = one
    activation-sized all-reduce — no replicated token copies, no scatter
    collectives. Requires an ambient mesh (``jax.set_mesh``) and
    n_experts % ep_shards == 0; differentiable (psum^T = psum).
    """
    import jax as _jax

    k = experts_per_token

    def local(x_loc, router, gate, up, down):
        b_loc, s, d = x_loc.shape
        t_loc = b_loc * s
        e_loc = gate.shape[0]
        ej = _jax.lax.axis_index(ep_axis)
        x2 = x_loc.reshape(t_loc, d)
        logits = (x2 @ router.astype(x2.dtype)).astype(jnp.float32)  # (T, E)
        probs = _jax.nn.softmax(logits, axis=-1)
        gate_vals, exp_idx = _jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        fe = exp_idx.T.reshape(-1)  # (kT,) global expert ids
        le = fe - ej * e_loc
        in_local = (le >= 0) & (le < e_loc)
        le_c = jnp.clip(le, 0, e_loc - 1)
        oh = jnp.where(in_local[:, None],
                       _jax.nn.one_hot(le_c, e_loc, dtype=jnp.int32), 0)
        pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
        cap = max(1, int(t_loc * k * capacity_factor / n_experts))
        keep = in_local & (pos < cap)
        pos_c = jnp.minimum(pos, cap - 1)

        vals = jnp.where(keep[:, None], jnp.tile(x2, (k, 1)), 0)
        buf = jnp.zeros((e_loc, cap, d), x2.dtype).at[le_c, pos_c].add(vals)
        h = _jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate.astype(x2.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, up.astype(x2.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, down.astype(x2.dtype))

        out_flat = y[le_c, pos_c]
        gv = gate_vals.T.reshape(-1)
        out_flat = jnp.where(keep[:, None], out_flat * gv[:, None].astype(x2.dtype), 0)
        out = out_flat.reshape(k, t_loc, d).sum(axis=0)
        out = _jax.lax.psum(out, ep_axis)  # combine partial expert outputs

        frac_tokens = jnp.mean(_jax.nn.one_hot(exp_idx[:, 0], n_experts, dtype=jnp.float32), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        aux = n_experts * jnp.sum(frac_tokens * frac_probs)
        aux = _jax.lax.pmean(aux, dp_axes)
        return out.reshape(b_loc, s, d), aux

    fn = _jax.shard_map(
        local,
        in_specs=(
            P(dp_axes, None, None),
            P(),  # router replicated
            P(ep_axis, None, None),
            P(ep_axis, None, None),
            P(ep_axis, None, None),
        ),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["gate"], p["up"], p["down"])


def moe_init(rng, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32):
    r = jax.random.split(rng, 4)
    s_in = d_model**-0.5
    s_ff = d_ff**-0.5
    return {
        "router": jax.random.normal(r[0], (d_model, n_experts), dtype) * s_in,
        "gate": jax.random.normal(r[1], (n_experts, d_model, d_ff), dtype) * s_in,
        "up": jax.random.normal(r[2], (n_experts, d_model, d_ff), dtype) * s_in,
        "down": jax.random.normal(r[3], (n_experts, d_ff, d_model), dtype) * s_ff,
    }


def moe_apply(
    p,
    x: jax.Array,  # (B, S, D)
    *,
    n_experts: int,
    experts_per_token: int,
    capacity_factor: float = 1.25,
    ep_spec: P | None = None,  # expert-buffer sharding, e.g. P('model', None, None)
    dispatch_chunks: int = 1,  # SHOULD equal the DP shard count under pjit
    tok_spec: P | None = None,  # token-chunk sharding, e.g. P(None, dp, None)
):
    """Top-k routed MoE.

    dispatch_chunks > 1 enables SHARD-LOCAL dispatch: tokens are viewed as
    (chunks, T/chunks) with the position-in-expert prefix-sum computed per
    chunk and per-chunk expert capacity. With chunks == dp shard count, the
    cumsum never crosses shard boundaries, so GSPMD keeps routing math local
    and the only cross-shard movement is the token scatter into the
    expert-sharded buffer (the all-to-all) — without this, the global cumsum
    forces GSPMD to replicate (kT, D) token copies on every device
    (§Perf arctic iteration 1: 281s -> collective term, 68 TB/device of
    replicated selects).
    """
    b, s, d = x.shape
    t = b * s
    k = experts_per_token
    tc = max(1, dispatch_chunks)
    if t % tc != 0:  # tiny decode batches: fall back to one chunk
        tc = 1
    tl = t // tc
    cap = max(1, int(tl * k * capacity_factor / n_experts))

    x3 = x.reshape(tc, tl, d)
    if tc > 1:
        x3 = constrain(x3, tok_spec)
    logits = (x3 @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (tc, Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, exp_idx = jax.lax.top_k(probs, k)  # (tc, Tl, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # slot-major within each chunk: first choices get dispatch priority
    fe = exp_idx.transpose(0, 2, 1).reshape(tc, k * tl)  # (tc, kTl)
    oh = jax.nn.one_hot(fe, n_experts, dtype=jnp.int32)  # (tc, kTl, E)
    pos = jnp.sum((jnp.cumsum(oh, axis=1) - 1) * oh, axis=2)  # chunk-local
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    vals = jnp.tile(x3, (1, k, 1))  # (tc, kTl, D)
    vals = jnp.where(keep[..., None], vals, 0)
    if tc > 1:
        vals = constrain(vals, tok_spec)
    cidx = jnp.broadcast_to(jnp.arange(tc)[:, None], fe.shape)
    buf = jnp.zeros((n_experts, tc, cap, d), x.dtype).at[fe, cidx, pos_c].add(vals)
    # ep_spec is the 4-D (E, chunks, cap, D) buffer spec, e.g.
    # P('model', dp, None, None): experts over TP, token chunks over DP —
    # the scatter above becomes the canonical MoE all-to-all.
    buf = constrain(buf, ep_spec)

    h = jax.nn.silu(jnp.einsum("etcd,edf->etcf", buf, p["gate"].astype(x.dtype)))
    h = h * jnp.einsum("etcd,edf->etcf", buf, p["up"].astype(x.dtype))
    y = jnp.einsum("etcf,efd->etcd", h, p["down"].astype(x.dtype))
    y = constrain(y, ep_spec)

    out_flat = y[fe, cidx, pos_c]  # (tc, kTl, D)
    if tc > 1:
        out_flat = constrain(out_flat, tok_spec)
    gates_flat = gate_vals.transpose(0, 2, 1).reshape(tc, k * tl)
    out_flat = jnp.where(keep[..., None], out_flat * gates_flat[..., None].astype(x.dtype), 0)
    out = out_flat.reshape(tc, k, tl, d).sum(axis=1).reshape(t, d)

    # Switch load-balance aux loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(
        jax.nn.one_hot(exp_idx[..., 0].reshape(-1), n_experts, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs.reshape(-1, n_experts), axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return out.reshape(b, s, d), aux
