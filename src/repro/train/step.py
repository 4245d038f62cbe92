"""The jitted train step: loss -> grad -> clip -> AdamW, with explicit
in/out shardings (this is the function the multi-pod dry-run lowers)."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.sharding.rules import Rules


@dataclass
class TrainState:
    params: Any
    opt: dict
    step: int = 0


def batch_specs(model: Model, batch: int, *, with_embeddings: bool = False,
                with_mrope: bool = False) -> dict:
    r = model.rules
    dp = r.dp(batch)
    specs = {"labels": P(dp, None)}
    if with_embeddings:
        specs["embeddings"] = P(dp, None, None)
    else:
        specs["tokens"] = P(dp, None)
    if with_mrope:
        specs["mrope_pos"] = P(dp, None, None)
    return specs


def make_train_step(model: Model, opt_cfg: AdamWConfig,
                    lr_fn: Optional[Callable] = None):
    """Returns train_step(params, opt_state, batch) -> (params', opt', metrics).

    Not yet jitted — callers wrap with jax.jit and the sharding/donation
    policy they want (see repro.launch.dryrun / repro.train.loop).
    """

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.train_loss, has_aux=True)(params, batch)
        lr = lr_fn(opt_state["step"]) if lr_fn is not None else opt_cfg.lr
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg, lr=lr)
        metrics = dict(metrics, loss=loss, lr=jnp.asarray(lr), **opt_metrics)
        return params, opt_state, metrics

    return train_step


def state_shardings(model: Model) -> tuple:
    """(params, AdamW state) NamedSharding trees on the model's mesh."""
    pspecs = model.param_specs()
    return model.rules.named_tree(
        (pspecs, {"mu": pspecs, "nu": pspecs, "step": P()}))


def jit_train_step(model: Model, opt_cfg: AdamWConfig, batch: int,
                   lr_fn: Optional[Callable] = None, *, donate: bool = True,
                   with_embeddings: bool = False, with_mrope: bool = False):
    """Fully-specified jit of the train step for the model's mesh."""
    step_fn = make_train_step(model, opt_cfg, lr_fn)
    pshard, oshard = state_shardings(model)
    bspecs = batch_specs(model, batch, with_embeddings=with_embeddings,
                         with_mrope=with_mrope)
    return jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, model.rules.named_tree(bspecs)),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1) if donate else (),
    )


def init_train_state(model: Model, key) -> TrainState:
    params = model.init(key)
    return TrainState(params=params, opt=adamw_init(params))
