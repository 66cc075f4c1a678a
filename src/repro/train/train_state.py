"""Train state + the jit-able train/serve step factories used everywhere
(trainer, dry-run, benchmarks)."""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.optim.optimizers import Optimizer, clip_by_global_norm


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array  # () int32


def init_train_state(key: jax.Array, cfg: ModelConfig, opt: Optimizer) -> TrainState:
    params = lm.init_lm(key, cfg)
    return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))


def make_train_step(cfg: ModelConfig, opt: Optimizer, lr_schedule, *,
                    grad_clip: float = 1.0):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def train_step(state: TrainState, batch: dict):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch), has_aux=True
        )(state.params)
        if grad_clip:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            metrics = dict(metrics, grad_norm=gnorm)
        lr = lr_schedule(state.step)
        new_params, new_opt = opt.update(grads, state.opt_state, state.params, lr)
        metrics = dict(metrics, lr=lr)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_grad_accum_train_step(cfg: ModelConfig, opt: Optimizer, lr_schedule, *,
                               accum: int, grad_clip: float = 1.0):
    """Gradient-accumulated step: batch dims are (accum, micro_batch, ...).

    Used by the elastic plan to preserve global batch on fewer devices.
    """

    def train_step(state: TrainState, batch: dict):
        def micro(i, carry):
            grads, loss_sum = carry
            mb = jax.tree.map(lambda a: a[i], batch)
            (loss, _), g = jax.value_and_grad(
                lambda p: lm.loss_fn(p, cfg, mb), has_aux=True
            )(state.params)
            return jax.tree.map(jnp.add, grads, g), loss_sum + loss

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        grads, loss_sum = jax.lax.fori_loop(0, accum, micro, (zeros, jnp.zeros(())))
        grads = jax.tree.map(lambda g: g / accum, grads)
        if grad_clip:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = lr_schedule(state.step)
        new_params, new_opt = opt.update(grads, state.opt_state, state.params, lr)
        return TrainState(new_params, new_opt, state.step + 1), {
            "loss": loss_sum / accum, "lr": lr,
        }

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch, caches):
        logits, caches = lm.prefill(
            params, cfg, batch["tokens"], caches,
            context=batch.get("context"),
        )
        # next-token for the last position of every request
        return jnp.argmax(logits[:, -1, :], axis=-1), caches

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """decode: one new token against a KV cache of fixed length."""

    def serve_step(params, caches, batch):
        logits, caches = lm.decode_step(
            params, cfg, batch["token"], caches, batch["pos"],
            context=batch.get("context"),
        )
        return jnp.argmax(logits[:, -1, :], axis=-1), caches

    return serve_step
