"""Training step factory: loss, grads, clipping, optimizer, microbatching.

Two step variants:
  * ``make_train_step``          — pjit-style: gradients reduce via GSPMD's
    implicit collectives (the 40-cell dry-run lowers this one).
  * ``make_shardmap_train_step`` — explicit-DP shard_map: per-shard grads,
    int8-compressed psum over the data axes (grad compression for slow
    inter-pod links), then a replicated optimizer step. Demonstrates the
    distributed-optimization path; validated against the pjit variant.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.model import ArchConfig, forward
from repro.optim import clip_by_global_norm, compressed_psum
from repro.train.losses import cross_entropy
from repro.utils import register_pytree_dataclass


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def train_state_init(rng, cfg: ArchConfig, opt_init) -> TrainState:
    from repro.models.model import init_params

    params = init_params(rng, cfg)
    return TrainState(params=params, opt_state=opt_init(params), step=jnp.zeros((), jnp.int32))


def loss_fn(params, cfg: ArchConfig, batch: dict, aux_weight: float = 0.01):
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    loss = cross_entropy(logits, labels) + aux_weight * aux
    return loss, aux


def make_train_step(
    cfg: ArchConfig,
    optimizer,
    lr_schedule: Callable,
    *,
    grad_clip: float = 1.0,
    microbatches: int = 1,
    donate: bool = True,
    jit_compile: bool = True,
):
    """Returns train_step(state, batch) -> (state, metrics)."""
    _opt_init, opt_update = optimizer

    def grads_of(params, batch):
        if microbatches == 1:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, batch)
            return loss, aux, grads
        # gradient accumulation over leading micro-split
        def mb(carry, mbatch):
            loss_a, aux_a, g_a = carry
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, mbatch)
            g_a = jax.tree.map(lambda a, b: a + b, g_a, g)
            return (loss_a + loss, aux_a + aux, g_a), None

        split = jax.tree.map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:]),
            batch,
        )
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, aux, grads), _ = jax.lax.scan(mb, (0.0, 0.0, zero_g), split)
        inv = 1.0 / microbatches
        return loss * inv, aux * inv, jax.tree.map(lambda g: g * inv, grads)

    def train_step(state: TrainState, batch: dict):
        loss, aux, grads = grads_of(state.params, batch)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = lr_schedule(state.step)
        updates, opt_state = opt_update(grads, state.opt_state, state.params, lr)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state.params, updates)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm, "lr": lr}
        return TrainState(params=params, opt_state=opt_state, step=state.step + 1), metrics

    if not jit_compile:
        return train_step
    if donate:
        return jax.jit(train_step, donate_argnums=(0,))
    return jax.jit(train_step)


def make_shardmap_train_step(
    cfg: ArchConfig,
    optimizer,
    lr_schedule: Callable,
    mesh,
    *,
    data_axes=("data",),
    grad_clip: float = 1.0,
    compress_grads: bool = True,
):
    """Explicit-DP training step: batch sharded over `data_axes`, params
    replicated, int8-compressed gradient psum (see optim/compression.py)."""
    _opt_init, opt_update = optimizer

    def local_step(state: TrainState, batch: dict):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params, cfg, batch)
        grads = compressed_psum(grads, data_axes, enabled=compress_grads)
        nshards = 1
        for ax in data_axes:
            nshards *= jax.lax.axis_size(ax)
        grads = jax.tree.map(lambda g: g / nshards, grads)
        loss = jax.lax.pmean(loss, data_axes)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = lr_schedule(state.step)
        updates, opt_state = opt_update(grads, state.opt_state, state.params, lr)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state.params, updates)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm, "lr": lr}
        return TrainState(params=params, opt_state=opt_state, step=state.step + 1), metrics

    state_specs = None  # replicated
    batch_spec = jax.tree.map(lambda _: P(data_axes), {"tokens": 0, "labels": 0})

    def wrapped(state, batch):
        fn = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state), {k: P(data_axes) for k in batch}),
            out_specs=(jax.tree.map(lambda _: P(), state), {"loss": P(), "aux_loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False,
        )
        return fn(state, batch)

    return jax.jit(wrapped)
