"""Optimizer factory.

Parity: deepspeed/ops/adam (FusedAdam), lion, adagrad, lamb, sgd — the
reference's fused CUDA multi-tensor kernels become optax transforms whose
update math XLA fuses into the sharded train step. 1-bit optimizers live
in ops/onebit.py.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from ..config import OptimizerConfig


def _lamb(learning_rate, b1=0.9, b2=0.999, eps=1e-6, weight_decay=0.0):
    """LAMB (reference: deepspeed/ops/lamb/fused_lamb.py semantics)."""
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
        optax.add_decayed_weights(weight_decay),
        optax.scale_by_trust_ratio(),
        optax.scale_by_learning_rate(learning_rate),
    )


def build_optimizer(
    cfg: OptimizerConfig, lr_schedule: Callable
) -> optax.GradientTransformation:
    """Build the optax transform from an "optimizer" config section.

    The learning rate enters through ``_scale_by_schedule_positive`` (its
    state carries the update count); the engine reports the live lr by
    evaluating the same schedule at the state's step counter.
    """
    name = cfg.type.lower().replace("_", "")
    p = dict(cfg.params)
    p.pop("lr", None)
    betas = cfg.betas
    common = dict(b1=betas[0], b2=betas[1], eps=cfg.eps)

    if name in ("adam", "adamw", "fusedadam"):
        base = optax.chain(
            optax.scale_by_adam(**common),
            optax.add_decayed_weights(cfg.weight_decay),
            optax.scale(-1.0),
        )
        tx = optax.chain(base, _scale_by_schedule_positive(lr_schedule))
    elif name == "lion":
        tx = optax.chain(
            optax.scale_by_lion(b1=betas[0], b2=betas[1]),
            optax.add_decayed_weights(cfg.weight_decay),
            optax.scale(-1.0),
            _scale_by_schedule_positive(lr_schedule),
        )
    elif name == "adagrad":
        tx = optax.chain(
            optax.scale_by_rss(initial_accumulator_value=p.get("initial_accumulator_value", 0.1)),
            optax.add_decayed_weights(cfg.weight_decay),
            optax.scale(-1.0),
            _scale_by_schedule_positive(lr_schedule),
        )
    elif name in ("lamb", "fusedlamb"):
        tx = optax.chain(
            optax.scale_by_adam(**common),
            optax.add_decayed_weights(cfg.weight_decay),
            optax.scale_by_trust_ratio(),
            optax.scale(-1.0),
            _scale_by_schedule_positive(lr_schedule),
        )
    elif name == "sgd":
        momentum = p.get("momentum", 0.0)
        tx = optax.chain(
            optax.trace(decay=momentum) if momentum else optax.identity(),
            optax.add_decayed_weights(cfg.weight_decay),
            optax.scale(-1.0),
            _scale_by_schedule_positive(lr_schedule),
        )
    elif name in ("onebitadam", "zerooneadam", "onebitlamb"):
        from ..ops.onebit import build_onebit_optimizer

        tx = build_onebit_optimizer(name, cfg, lr_schedule)
    else:
        raise KeyError(f"unknown optimizer type {cfg.type!r}")
    return tx


def _scale_by_schedule_positive(schedule: Callable) -> optax.GradientTransformation:
    """Like optax.scale_by_schedule but multiplies by +schedule(step) (sign is
    applied upstream so the live lr we report stays positive)."""

    def init_fn(params):
        del params
        return optax.ScaleByScheduleState(count=jnp.zeros([], jnp.int32))

    def update_fn(updates, state, params=None):
        del params
        lr = schedule(state.count)
        updates = jax.tree.map(lambda g: g * lr.astype(g.dtype), updates)
        return updates, optax.ScaleByScheduleState(count=state.count + 1)

    return optax.GradientTransformation(init_fn, update_fn)


def current_lr(schedule: Callable, step: int) -> float:
    return float(schedule(jnp.asarray(step, jnp.int32)))
