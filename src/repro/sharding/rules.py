"""Sharding rules: FSDP x TP (x pod) partition specs for every param family.

Strategy (DESIGN.md §4):
  * parameters: FSDP — the ``d_model``-dim over the data axes, feature /
    head-flattened dims over ``model`` (GSPMD all-gathers at use);
  * activations: batch over data axes, feature dims over ``model``;
  * attention: *sequence*-sharded over ``model`` inside a shard_map island
    (head counts like yi-34b's 56 do not divide a 16-way model axis; sequence
    always does for the assigned shapes).  Decode uses a distributed online
    softmax over the sequence-sharded KV cache;
  * MoE: shard_map island, ``tp`` (hidden dim) or ``ep`` (expert dim) over
    ``model`` — see repro.models.moe;
  * multi-pod: the ``pod`` axis is prepended to the data axes, so global
    batch shards over pod x data and FSDP gathers cross the pod boundary.

``Rules`` is the single object the model, steps, and dry-run share.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig


@dataclass
class Rules:
    mesh: Mesh
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    moe_sharding: str = "tp"          # "tp" | "ep" (§Perf knob)
    remat: bool = True                # activation checkpointing for train
    # attention chunking (flash-style scan block sizes; §Perf knob)
    q_chunk: int = 1024
    k_chunk: int = 1024
    # skip fully-masked KV blocks at runtime (causal/window early-out)
    skip_masked_blocks: bool = True
    # §Perf knobs (beyond-paper optimizations; defaults = faithful baseline)
    # cast fp32 master params to bf16 BEFORE the FSDP gather boundary, so
    # per-layer all-gathers move half the bytes
    param_gather_dtype: str = "float32"     # "float32" | "bfloat16"
    # run the SSD intra-chunk einsums in bf16 (decay/cumsum stay fp32)
    ssd_compute_dtype: str = "float32"      # "float32" | "bfloat16"
    # override the SSD chunk length (0 = use the config's chunk_size)
    ssm_chunk: int = 0
    # DECODE-ONLY serving layout: params pure-TP over BOTH mesh axes
    # (no FSDP dim -> no per-token parameter all-gathers), batch
    # replicated across data, KV cache sequence dim sharded over all axes
    serving_layout: bool = False
    # Megatron-style sequence parallelism: keep activations sequence-
    # sharded over the model axis BETWEEN layers (norms/elementwise run
    # local; the attention islands already consume exactly this layout, so
    # their boundary resharding disappears and the MLP all-reduce becomes
    # all-gather + reduce-scatter).  §Perf iteration 3 — measured to be
    # the actual fix for the activation-dominated collective term.
    seq_sharded_acts: bool = False

    # ----- axis sizes -----

    @property
    def data_size(self) -> int:
        import math
        return math.prod(self.mesh.shape[a] for a in self.data_axes)

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def all_axes(self) -> tuple[str, ...]:
        return (*self.data_axes, self.model_axis)

    @property
    def total_size(self) -> int:
        return self.data_size * self.model_size

    @property
    def axis_sizes(self) -> dict:
        """{axis name: mesh size} — the form the restore planner consumes
        (see repro.ckpt.plan.dim_slices_for_spec)."""
        return dict(self.mesh.shape)

    def coords_of_rank(self, rank: int) -> dict:
        """Mesh coordinates of flat device ``rank`` (C order over
        ``axis_names``): the per-axis indices a restore planner needs to
        slice this host's shard of every checkpointed tensor."""
        coords = {}
        rem = int(rank)
        for a in reversed(self.mesh.axis_names):
            n = int(self.mesh.shape[a])
            coords[a] = rem % n
            rem //= n
        return coords

    # ----- spec helpers -----

    def dp(self, n: int):
        """Data-axes spec for a dim of size n (None if not shardable).
        Serving layout: batch/d_model replicate (no FSDP dim)."""
        if self.serving_layout:
            return None
        return self.data_axes if n % max(self.data_size, 1) == 0 else None

    def tp(self, n: int):
        """Feature-dim spec.  Serving layout: both axes when divisible."""
        if self.serving_layout and n % max(self.total_size, 1) == 0:
            return self.all_axes
        return self.model_axis if n % max(self.model_size, 1) == 0 else None

    @property
    def cache_axes(self) -> tuple[str, ...]:
        """Axes sharding the KV-cache sequence dim."""
        return self.all_axes if self.serving_layout else (self.model_axis,)

    @property
    def reduce_axes(self) -> tuple[str, ...]:
        """Axes a feature-sharded contraction reduces over."""
        return self.all_axes if self.serving_layout else (self.model_axis,)

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def named_tree(self, specs):
        """NamedSharding pytree congruent with a PartitionSpec pytree."""
        return jax.tree.map(self.named, specs,
                            is_leaf=lambda x: isinstance(x, P))

    def constrain(self, x, spec: P):
        return jax.lax.with_sharding_constraint(x, self.named(spec))

    # ----- activation specs -----

    def act_btd(self, batch: int, seq: int = 0) -> P:
        """[B, S, D] activations.  With ``seq_sharded_acts`` the sequence
        dim shards over the model axis (pass ``seq``; falls back to
        replicated when not divisible, e.g. decode's S=1)."""
        if self.seq_sharded_acts and seq and \
                seq % max(self.model_size, 1) == 0:
            return P(self.dp(batch), self.model_axis, None)
        return P(self.dp(batch), None, None)

    def act_logits(self, batch: int, vocab: int = 0) -> P:
        """[B, S, V] logits (vocab feature-sharded)."""
        v = self.tp(vocab) if vocab else self.model_axis
        return P(self.dp(batch), None, v)

    def act_ff(self, batch: int) -> P:
        """[B, S, F] MLP hidden."""
        return P(self.dp(batch), None, self.model_axis)

    def seq_attn(self, batch: int) -> P:
        """[B, S, H, Dh] q/k/v inside sequence-sharded attention."""
        return P(self.dp(batch), self.model_axis, None, None)

    def kv_cache(self, batch: int) -> P:
        """[B, W, Hkv, Dh] cache: window/sequence dim over model."""
        return P(self.dp(batch), self.model_axis, None, None)

    def ssm_state(self, batch: int) -> P:
        """[B, H, P, N] SSM state: heads over model."""
        return P(self.dp(batch), self.model_axis, None, None)

    # ----- parameter specs -----

    def param_specs(self, cfg: ModelConfig) -> dict:
        """PartitionSpec pytree congruent with Model.init(cfg) params."""
        from repro.models.model import param_schema
        schema = param_schema(cfg, self)
        return jax.tree.map(lambda leaf: leaf.spec, schema,
                            is_leaf=lambda x: hasattr(x, "spec"))


def make_rules(mesh: Mesh, *, moe_sharding: str = "tp", **kw) -> Rules:
    axes = mesh.axis_names
    if "pod" in axes:
        data_axes: tuple[str, ...] = ("pod", "data")
    else:
        data_axes = ("data",)
    return Rules(mesh=mesh, data_axes=data_axes, moe_sharding=moe_sharding,
                 **kw)


def single_device_rules(**kw) -> Rules:
    """A (1, 1) mesh over ("data", "model") for CPU smoke tests."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return make_rules(mesh, **kw)
