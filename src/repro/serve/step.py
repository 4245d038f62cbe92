"""Jitted prefill / decode steps with explicit shardings (these are the
functions the decode-shape dry-runs lower)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.model import Model


def jit_prefill(model: Model, batch: int, cache_len: int, *,
                with_embeddings: bool = False, with_mrope: bool = False):
    r = model.rules
    dp = r.dp(batch)
    bspecs: dict = {}
    if with_embeddings:
        bspecs["embeddings"] = P(dp, None, None)
    else:
        bspecs["tokens"] = P(dp, None)
    if with_mrope:
        bspecs["mrope_pos"] = P(dp, None, None)
    pspecs = model.param_specs()
    cspecs = model.cache_specs(batch, cache_len)

    def fn(params, batch_in):
        return model.prefill(params, batch_in, cache_len=cache_len)

    return jax.jit(
        fn,
        in_shardings=(r.named_tree(pspecs), r.named_tree(bspecs)),
        out_shardings=(r.named(P(dp, r.tp(model.cfg.vocab_size))),
                       r.named_tree(cspecs)),
    )


def jit_decode_step(model: Model, batch: int, cache_len: int, *,
                    donate_cache: bool = True):
    r = model.rules
    dp = r.dp(batch)
    pspecs = model.param_specs()
    cspecs = model.cache_specs(batch, cache_len)
    return jax.jit(
        model.decode_step,
        in_shardings=(r.named_tree(pspecs), r.named(P(dp, None)),
                      r.named_tree(cspecs), r.named(P())),
        out_shardings=(r.named(P(dp, r.tp(model.cfg.vocab_size))),
                      r.named_tree(cspecs)),
        donate_argnums=(2,) if donate_cache else (),
    )
