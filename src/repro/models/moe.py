"""Mixture-of-experts block.

Dispatch is the sort-based "dropping" scheme (Switch-style capacity): tokens
are argsorted by assigned expert, positions within each expert group beyond
``capacity`` are dropped, experts run as one batched einsum, and results are
combined with the renormalized top-k router weights.  This keeps compiled
FLOPs proportional to *active* parameters (times the capacity factor) — a
dense all-experts formulation would inflate the roofline by E/k.

The block is a ``shard_map`` island inside the jitted step so the collective
pattern is explicit and auditable in the dry-run HLO:

  * ``tp`` sharding: every model-rank holds all experts with the FFN hidden
    dim sharded ``F/tp``; one ``psum`` over the model axis after combine.
  * ``ep`` sharding: experts sharded ``E/tp`` over the model axis; token
    activations are replicated over the model axis (they are sharded over
    data/pod only), so each rank dispatches the *same* local tokens to its
    own experts and the partial combines are ``psum``-reduced.  No all-to-all
    is needed because token-parallel and expert-parallel axes are orthogonal.

Both variants produce identical math (tested); they differ only in collective
schedule and per-rank matmul shapes — exactly the knob §Perf hillclimbs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig


def router_topk(x32: jax.Array, router_w: jax.Array, k: int):
    """Top-k routing with renormalized gates.  x32: [T, D] fp32."""
    logits = x32 @ router_w.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # [T, k]
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    return probs, gate, idx


def load_balance_aux(probs: jax.Array, idx: jax.Array, num_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    t = probs.shape[0]
    onehot = jax.nn.one_hot(idx[:, 0], num_experts, dtype=jnp.float32)
    f = jnp.mean(onehot, axis=0)          # fraction of tokens (1st choice)
    p = jnp.mean(probs, axis=0)           # mean router prob
    del t
    return num_experts * jnp.sum(f * p)


def _dispatch_indices(idx: jax.Array, tokens: int, num_experts: int,
                      capacity: int):
    """Sort-based dispatch bookkeeping.

    Returns (sorted_expert, sorted_token, sorted_slot_in_expert, keep_mask),
    all [T*k].
    """
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                                   # [T*k]
    tok_id = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = tok_id[order]
    group_start = jnp.searchsorted(se, jnp.arange(num_experts), side="left")
    pos = jnp.arange(tokens * k, dtype=jnp.int32) - group_start[se]
    keep = pos < capacity
    return order, se, st, pos, keep


def _expert_ffn(xe: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array) -> jax.Array:
    """Batched per-expert SwiGLU: xe [E, C, D] -> [E, C, D] (partial if the
    hidden dim is sharded — caller psums)."""
    dt = xe.dtype
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(dt))


def capacity_for(tokens: int, moe: MoEConfig) -> int:
    return max(1, math.ceil(tokens * moe.experts_per_token
                            / moe.num_experts * moe.capacity_factor))


def moe_block_local(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
                    w_up: jax.Array, w_down: jax.Array, *, moe: MoEConfig,
                    model_axis: str, data_axes: tuple[str, ...],
                    moe_sharding: str = "tp",
                    reduce_axes: tuple[str, ...] = ()):
    """Per-shard MoE block body (runs inside shard_map).

    x: [T_local, D] (tokens local to the data shard, replicated over model).
    Weights: tp -> [E, D, F/tp]; ep -> [E/tp, D, F].
    ``reduce_axes``: axes the hidden dim is sharded over in tp mode
    (default just the model axis; the serving layout adds the data axes).
    Returns (y [T_local, D] fully reduced, aux loss scalar replicated).
    """
    reduce_axes = reduce_axes or (model_axis,)
    t, d = x.shape
    e, k = moe.num_experts, moe.experts_per_token
    cap = capacity_for(t, moe)

    x32 = x.astype(jnp.float32)
    probs, gate, idx = router_topk(x32, router_w, k)
    aux = load_balance_aux(probs, idx, e)
    aux = jax.lax.pmean(aux, data_axes)

    order, se, st, pos, keep = _dispatch_indices(idx, t, e, cap)
    sg = gate.reshape(-1)[order]

    if moe_sharding == "ep":
        n_shards = jax.lax.axis_size(model_axis)
        rank = jax.lax.axis_index(model_axis)
        e_loc = e // n_shards
        off = rank * e_loc
        local = keep & (se >= off) & (se < off + e_loc)
        dest = jnp.where(local, (se - off) * cap + pos, e_loc * cap)  # OOB=drop
        rows = e_loc * cap
    else:
        local = keep
        dest = jnp.where(local, se * cap + pos, e * cap)
        rows = e * cap

    # scatter tokens into expert buffers ([rows, D]); OOB indices drop
    gathered = jnp.where(local[:, None], x[st], 0)
    xe = jnp.zeros((rows, d), x.dtype).at[dest].add(
        gathered, mode="drop")
    xe = xe.reshape(-1, cap, d)

    ye = _expert_ffn(xe, w_gate, w_up, w_down).reshape(rows, d)

    # combine with gates back to token order (partial: hidden-shard for tp,
    # expert-shard for ep), then reduce over the model axis.
    contrib = jnp.where(local[:, None], sg[:, None].astype(ye.dtype)
                        * ye.at[dest, :].get(mode="fill", fill_value=0), 0)
    y = jnp.zeros((t, d), ye.dtype).at[st].add(contrib)
    y = jax.lax.psum(y, model_axis if moe_sharding == "ep" else reduce_axes)
    return y.astype(x.dtype), aux


def make_sharded_moe(mesh, *, moe: MoEConfig, model_axis: str = "model",
                     data_axes: tuple[str, ...] = ("data",),
                     moe_sharding: str = "tp", batch_spec="__default__",
                     feature_axes: tuple[str, ...] = ()):
    """Wrap the local block in shard_map for the given mesh.

    Token arrays come in as [B, S, D] sharded over data axes on batch; the
    wrapper flattens to local tokens.  ``batch_spec`` overrides the batch-dim
    sharding (None when the global batch doesn't divide the data axes, e.g.
    long_500k's batch of 1 — tokens then replicate across data shards).
    Expert weights: see moe_block_local.
    """
    from jax.sharding import PartitionSpec as P

    if batch_spec == "__default__":
        batch_spec = data_axes
    feature_axes = feature_axes or (model_axis,)

    if moe_sharding == "ep":
        wspec = P(model_axis, None, None)
        wspec_down = P(model_axis, None, None)
    else:
        wspec = P(None, None, feature_axes)
        wspec_down = P(None, feature_axes, None)

    body = partial(moe_block_local, moe=moe, model_axis=model_axis,
                   data_axes=data_axes, moe_sharding=moe_sharding,
                   reduce_axes=feature_axes)

    def flat_body(xbsd, rw, wg, wu, wd):
        b, s, d = xbsd.shape
        y, aux = body(xbsd.reshape(b * s, d), rw, wg, wu, wd)
        return y.reshape(b, s, d), aux

    return jax.shard_map(
        flat_body,
        mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  wspec, wspec, wspec_down),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False,
    )
