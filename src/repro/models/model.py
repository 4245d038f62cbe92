"""The model family: decoder-only LM over six architecture types
(dense / moe / ssm / hybrid / vlm / audio), pure JAX, scan-over-layers.

Distribution strategy (see repro.sharding.rules):
  * matmuls / norms / embeddings: GSPMD via sharding constraints;
  * attention: sequence-sharded shard_map islands (prefill/train: q over the
    model axis with gathered KV; decode: distributed online softmax over the
    sequence-sharded KV cache);
  * MoE: shard_map island (repro.models.moe), tp or ep expert sharding.

Three entry points, matching the assigned shapes:
  ``train_loss``   — tokens/embeddings -> mean CE (+ MoE aux);
  ``prefill``      — fills a KV/SSM cache, returns last-token logits;
  ``decode_step``  — ONE new token against a seq_len cache.
"""

from __future__ import annotations

import dataclasses
import math
from collections import namedtuple
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.sharding.rules import Rules

Leaf = namedtuple("Leaf", ["shape", "spec", "init"])


def _normal(scale: float):
    def init(key, shape):
        return scale * jax.random.normal(key, shape, jnp.float32)
    return init


def _ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def _zeros(key, shape):
    return jnp.zeros(shape, jnp.float32)


def _a_log_init(key, shape):
    # A uniformly in [1, 16] (Mamba2 default)
    a = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
    return jnp.log(a)


def _dt_bias_init(key, shape):
    # dt in [1e-3, 1e-1] log-uniform, stored as inverse-softplus
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# ---------------------------------------------------------------------------
# Parameter schema (shapes + shardings + init), single source of truth
# ---------------------------------------------------------------------------

def _attn_leaves(cfg: ModelConfig, r: Rules, stacked: bool) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    pre = (cfg.num_layers,) if stacked else ()
    lp = (None,) if stacked else ()
    s_in = _normal(0.02)
    s_out = _normal(0.02 / math.sqrt(2 * cfg.num_layers))
    leaves = {
        "attn_norm": Leaf(pre + (d,), P(*lp, None), _ones),
        "wq": Leaf(pre + (d, nq * hd), P(*lp, r.dp(d), r.tp(nq * hd)), s_in),
        "wk": Leaf(pre + (d, nkv * hd), P(*lp, r.dp(d), r.tp(nkv * hd)), s_in),
        "wv": Leaf(pre + (d, nkv * hd), P(*lp, r.dp(d), r.tp(nkv * hd)), s_in),
        "wo": Leaf(pre + (nq * hd, d), P(*lp, r.tp(nq * hd), r.dp(d)), s_out),
    }
    if cfg.qkv_bias:
        leaves["bq"] = Leaf(pre + (nq * hd,), P(*lp, r.tp(nq * hd)), _zeros)
        leaves["bk"] = Leaf(pre + (nkv * hd,), P(*lp, r.tp(nkv * hd)), _zeros)
        leaves["bv"] = Leaf(pre + (nkv * hd,), P(*lp, r.tp(nkv * hd)), _zeros)
    return leaves


def _mlp_leaves(cfg: ModelConfig, r: Rules) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    lcount = cfg.num_layers
    s_in = _normal(0.02)
    s_out = _normal(0.02 / math.sqrt(2 * lcount))
    base = {"mlp_norm": Leaf((lcount, d), P(None, None), _ones)}
    if cfg.moe:
        e = cfg.moe.num_experts
        if r.moe_sharding == "ep" and e % r.model_size == 0:
            espec = (r.model_axis, r.dp(d), None)
            espec_dn = (r.model_axis, None, r.dp(d))
        else:
            espec = (None, r.dp(d), r.tp(f))
            espec_dn = (None, r.tp(f), r.dp(d))
        base.update({
            "router": Leaf((lcount, d, e), P(None, r.dp(d), None), s_in),
            "w_gate": Leaf((lcount, e, d, f), P(None, *espec), s_in),
            "w_up": Leaf((lcount, e, d, f), P(None, *espec), s_in),
            "w_down": Leaf((lcount, e, f, d), P(None, *espec_dn), s_out),
        })
    else:
        base.update({
            "w_gate": Leaf((lcount, d, f), P(None, r.dp(d), r.tp(f)), s_in),
            "w_up": Leaf((lcount, d, f), P(None, r.dp(d), r.tp(f)), s_in),
            "w_down": Leaf((lcount, f, d), P(None, r.tp(f), r.dp(d)), s_out),
        })
    return base


def _ssm_leaves(cfg: ModelConfig, r: Rules) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    h = di // s.head_dim
    gn = s.ngroups * s.state_dim
    lcount = cfg.num_layers
    s_in = _normal(0.02)
    s_out = _normal(0.02 / math.sqrt(2 * lcount))
    return {
        "norm": Leaf((lcount, d), P(None, None), _ones),
        "z_proj": Leaf((lcount, d, di), P(None, r.dp(d), r.tp(di)), s_in),
        "x_proj": Leaf((lcount, d, di), P(None, r.dp(d), r.tp(di)), s_in),
        "B_proj": Leaf((lcount, d, gn), P(None, r.dp(d), None), s_in),
        "C_proj": Leaf((lcount, d, gn), P(None, r.dp(d), None), s_in),
        "dt_proj": Leaf((lcount, d, h), P(None, r.dp(d), r.tp(h)), s_in),
        "conv_x_w": Leaf((lcount, s.conv_width, di), P(None, None, r.tp(di)),
                         _normal(0.2)),
        "conv_x_b": Leaf((lcount, di), P(None, r.tp(di)), _zeros),
        "conv_B_w": Leaf((lcount, s.conv_width, gn), P(None, None, None),
                         _normal(0.2)),
        "conv_B_b": Leaf((lcount, gn), P(None, None), _zeros),
        "conv_C_w": Leaf((lcount, s.conv_width, gn), P(None, None, None),
                         _normal(0.2)),
        "conv_C_b": Leaf((lcount, gn), P(None, None), _zeros),
        "A_log": Leaf((lcount, h), P(None, r.tp(h)), _a_log_init),
        "ssm_D": Leaf((lcount, h), P(None, r.tp(h)), _ones),
        "dt_bias": Leaf((lcount, h), P(None, r.tp(h)), _dt_bias_init),
        "gate_norm": Leaf((lcount, di), P(None, r.tp(di)), _ones),
        "out_proj": Leaf((lcount, di, d), P(None, r.tp(di), r.dp(d)), s_out),
    }


def param_schema(cfg: ModelConfig, r: Rules) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    schema: dict = {
        "embed": Leaf((v, d), P(r.tp(v), r.dp(d)), _normal(0.02)),
        "final_norm": Leaf((d,), P(None), _ones),
    }
    if not cfg.tie_embeddings:
        schema["lm_head"] = Leaf((d, v), P(r.dp(d), r.tp(v)), _normal(0.02))
    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        layers = _attn_leaves(cfg, r, stacked=True)
        layers.update(_mlp_leaves(cfg, r))
        schema["layers"] = layers
    elif cfg.arch_type == "ssm":
        schema["layers"] = _ssm_leaves(cfg, r)
    elif cfg.arch_type == "hybrid":
        schema["layers"] = _ssm_leaves(cfg, r)
        shared = _attn_leaves(
            dataclasses.replace(cfg, num_layers=1), r, stacked=False)
        schema["shared_attn"] = shared
    return schema


def _is_leaf(x):
    return isinstance(x, Leaf)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig, rules: Rules):
        self.cfg = cfg
        self.rules = rules
        self.compute_dtype = jnp.dtype(cfg.dtype)

    # ----- params -----

    def init(self, key) -> dict:
        schema = param_schema(self.cfg, self.rules)
        flat, tree = jax.tree.flatten(schema, is_leaf=_is_leaf)
        keys = jax.random.split(key, len(flat))
        vals = [leaf.init(k, leaf.shape) for k, leaf in zip(keys, flat)]
        return jax.tree.unflatten(tree, vals)

    def param_specs(self) -> dict:
        schema = param_schema(self.cfg, self.rules)
        return jax.tree.map(lambda leaf: leaf.spec, schema, is_leaf=_is_leaf)

    def param_shapes(self) -> dict:
        schema = param_schema(self.cfg, self.rules)
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, jnp.float32),
            schema, is_leaf=_is_leaf)

    def count_params(self) -> int:
        schema = param_schema(self.cfg, self.rules)
        return sum(math.prod(l.shape) for l in
                   jax.tree.leaves(schema, is_leaf=_is_leaf))

    # ----- attention shard_map islands -----

    def _seq_attn(self, batch: int, with_cache: bool, cache_w: int = 0,
                  seq_len: int = 0):
        """Prefill/train attention: q sequence-sharded over the model axis,
        KV gathered.  If ``with_cache``, also materializes the (sequence-
        sharded) KV cache for this layer."""
        cfg, r = self.cfg, self.rules
        window = cfg.sliding_window
        dp = r.dp(batch)
        tp = r.model_axis

        def body(q, k, v, q_pos, k_pos):
            out = attn.chunked_attention(
                q, k, v, q_pos, k_pos, window=window,
                q_chunk=r.q_chunk, k_chunk=r.k_chunk,
                skip_masked_blocks=r.skip_masked_blocks)
            if not with_cache:
                return out
            # build this shard's rows of the cache from the gathered k/v
            w, s = cache_w, seq_len
            w_loc = w // jax.lax.axis_size(tp)
            my0 = jax.lax.axis_index(tp) * w_loc
            g = my0 + jnp.arange(w_loc)
            p_start = max(0, s - w)
            src = p_start + jnp.mod(g - p_start, w)
            valid = src < s
            safe = jnp.clip(src, 0, s - 1)
            kc = jnp.where(valid[None, :, None, None], k[:, safe], 0)
            vc = jnp.where(valid[None, :, None, None], v[:, safe], 0)
            sp = jnp.where(valid, src, -1).astype(jnp.int32)
            return out, kc, vc, sp

        in_specs = (P(dp, tp, None, None), P(dp, None, None, None),
                    P(dp, None, None, None), P(tp), P(None))
        if with_cache:
            out_specs = (P(dp, tp, None, None), P(dp, tp, None, None),
                         P(dp, tp, None, None), P(tp))
        else:
            out_specs = P(dp, tp, None, None)
        return jax.shard_map(body, mesh=r.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _decode_attn(self, batch: int):
        """One-token decode with distributed online softmax over the
        sequence-sharded cache; also appends the new token's k/v.

        The cache sequence dim shards over ``rules.cache_axes`` — just the
        model axis normally, or ALL mesh axes in the serving layout."""
        cfg, r = self.cfg, self.rules
        window = cfg.sliding_window
        dp = r.dp(batch)
        axes = r.cache_axes
        n_shards = math.prod(r.mesh.shape[a] for a in axes)

        def body(q, k1, v1, kc, vc, sp, pos):
            # append: global slot -> local row (drop if not ours)
            w_loc = kc.shape[1]
            w = w_loc * n_shards
            # flattened shard index in PartitionSpec axis order
            my = jnp.int32(0)
            for a in axes:
                my = my * r.mesh.shape[a] + jax.lax.axis_index(a)
            slot = pos % w
            ls = slot - my * w_loc
            ls = jnp.where((ls >= 0) & (ls < w_loc), ls, w_loc)  # OOB drops
            kc = kc.at[:, ls].set(k1[:, 0].astype(kc.dtype), mode="drop")
            vc = vc.at[:, ls].set(v1[:, 0].astype(vc.dtype), mode="drop")
            sp = sp.at[ls].set(pos.astype(jnp.int32), mode="drop")

            # distributed online softmax
            b, _, hq, dh = q.shape
            hkv = kc.shape[2]
            g = hq // hkv
            qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
            s = jnp.einsum("bhgd,bkhd->bhgk", qg,
                           kc.astype(jnp.float32)) / math.sqrt(dh)
            ok = (sp >= 0) & (sp <= pos)
            if window > 0:
                ok &= sp > (pos - window)
            s = s + jnp.where(ok, 0.0, attn.NEG_INF)[None, None, None, :]
            m = jax.lax.pmax(jnp.max(s, axis=-1), axes)
            p = jnp.exp(s - m[..., None])
            l = jax.lax.psum(jnp.sum(p, axis=-1), axes)
            o = jax.lax.psum(
                jnp.einsum("bhgk,bkhd->bhgd", p,
                           vc.astype(jnp.float32)), axes)
            o = o / jnp.maximum(l, 1e-30)[..., None]
            out = o.reshape(b, 1, hq, dh).astype(q.dtype)
            return out, kc, vc, sp

        in_specs = (P(dp, None, None, None), P(dp, None, None, None),
                    P(dp, None, None, None), P(dp, axes, None, None),
                    P(dp, axes, None, None), P(axes), P())
        out_specs = (P(dp, None, None, None), P(dp, axes, None, None),
                     P(dp, axes, None, None), P(axes))
        return jax.shard_map(body, mesh=r.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # ----- attention sublayer -----

    def _qkv(self, p, a):
        cfg = self.cfg
        b, s, _ = a.shape
        dt = a.dtype
        q = a @ p["wq"].astype(dt)
        k = a @ p["wk"].astype(dt)
        v = a @ p["wv"].astype(dt)
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dt)
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        return q, k, v

    def _rope(self, x, positions, mrope_pos):
        cfg = self.cfg
        if cfg.mrope and mrope_pos is not None:
            return L.apply_mrope(x, mrope_pos, cfg.rope_theta,
                                 cfg.mrope_sections)
        return L.apply_rope(x, positions, cfg.rope_theta)

    def _act_seq(self, seq: int) -> int:
        """Sequence length to pass to act_btd: sequence-sharded residuals
        apply only to the attention families (the SSM conv/scan needs the
        full sequence locally)."""
        if self.cfg.arch_type in ("dense", "moe", "vlm", "audio"):
            return seq
        return 0

    def attention_sublayer(self, p, h, *, mode, cache, positions,
                           mrope_pos=None, cache_w: int = 0):
        """Returns (h', new_cache or None)."""
        cfg, r = self.cfg, self.rules
        b, s, d = h.shape
        a = L.rms_norm(h, p["attn_norm"], cfg.norm_eps)
        q, k, v = self._qkv(p, a)
        if mode != "decode":
            # settle into the sequence-sharded layout BEFORE RoPE so the
            # partitioner doesn't bounce through head-sharded intermediates
            seq_spec = P(r.dp(b), r.model_axis, None, None)
            q = r.constrain(q, seq_spec)
            k = r.constrain(k, seq_spec)
            v = r.constrain(v, seq_spec)
        rope_pos = positions if positions.ndim >= 1 else positions[None]
        q = self._rope(q, rope_pos, mrope_pos)
        k = self._rope(k, rope_pos, mrope_pos)

        new_cache = None
        if mode == "train":
            kpos = positions if positions.ndim == 1 else positions[0]
            out = self._seq_attn(b, with_cache=False)(q, k, v, kpos, kpos)
        elif mode == "prefill":
            kpos = positions if positions.ndim == 1 else positions[0]
            out, kc, vc, sp = self._seq_attn(
                b, with_cache=True, cache_w=cache_w, seq_len=s)(
                    q, k, v, kpos, kpos)
            new_cache = {"k": kc.astype(self.compute_dtype),
                         "v": vc.astype(self.compute_dtype), "slot_pos": sp}
        else:  # decode
            pos = positions if positions.ndim == 0 else positions.reshape(())
            out, kc, vc, sp = self._decode_attn(b)(
                q, k, v, cache["k"], cache["v"], cache["slot_pos"], pos)
            new_cache = {"k": kc, "v": vc, "slot_pos": sp}

        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        h = h + out @ p["wo"].astype(out.dtype)
        return r.constrain(h, r.act_btd(b, self._act_seq(s))), new_cache

    # ----- mlp / moe sublayer -----

    def mlp_sublayer(self, p, h):
        cfg, r = self.cfg, self.rules
        b = h.shape[0]
        m = L.rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        if cfg.moe:
            use_ep = (r.moe_sharding == "ep"
                      and cfg.moe.num_experts % r.model_size == 0)
            fax = r.tp(cfg.d_ff)
            feature_axes = fax if isinstance(fax, tuple) else (
                (fax,) if fax else (r.model_axis,))
            island = moe_lib.make_sharded_moe(
                r.mesh, moe=cfg.moe, model_axis=r.model_axis,
                data_axes=r.data_axes,
                moe_sharding="ep" if use_ep else "tp",
                batch_spec=r.dp(b), feature_axes=feature_axes)
            y, aux = island(m, p["router"], p["w_gate"], p["w_up"],
                            p["w_down"])
        else:
            y = L.swiglu(m, p["w_gate"], p["w_up"], p["w_down"])
            aux = jnp.zeros((), jnp.float32)
        h = h + y
        return r.constrain(h, r.act_btd(b, self._act_seq(h.shape[1]))), aux

    # ----- ssm sublayer -----

    def mamba_sublayer(self, p, h, *, mode, cache):
        cfg, r = self.cfg, self.rules
        s_cfg = cfg.ssm
        b, s, d = h.shape
        di = s_cfg.expand * d
        nh = di // s_cfg.head_dim
        pdim = s_cfg.head_dim
        g, n = s_cfg.ngroups, s_cfg.state_dim
        dt_c = h.dtype

        a = L.rms_norm(h, p["norm"], cfg.norm_eps)
        z = a @ p["z_proj"].astype(dt_c)
        x = a @ p["x_proj"].astype(dt_c)
        Bm = a @ p["B_proj"].astype(dt_c)
        Cm = a @ p["C_proj"].astype(dt_c)
        dtr = a @ p["dt_proj"].astype(dt_c)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))

        new_cache = None
        if mode in ("train", "prefill"):
            init_cx = cache["conv_x"] if cache is not None else None
            init_cb = cache["conv_B"] if cache is not None else None
            init_cc = cache["conv_C"] if cache is not None else None
            x, cx = ssm_lib.causal_conv(x, p["conv_x_w"], p["conv_x_b"], init_cx)
            Bm, cb = ssm_lib.causal_conv(Bm, p["conv_B_w"], p["conv_B_b"], init_cb)
            Cm, cc = ssm_lib.causal_conv(Cm, p["conv_C_w"], p["conv_C_b"], init_cc)
            dt = jax.nn.softplus(dtr.astype(jnp.float32)
                                 + p["dt_bias"].astype(jnp.float32))
            xh = x.reshape(b, s, nh, pdim)
            xh = r.constrain(xh, P(r.dp(b), None, r.tp(nh), None))
            y, state = ssm_lib.ssd_chunked(
                xh, dt, A, Bm.reshape(b, s, g, n), Cm.reshape(b, s, g, n),
                p["ssm_D"].astype(jnp.float32),
                chunk=r.ssm_chunk or s_cfg.chunk_size,
                init_state=cache["ssm"] if cache is not None else None,
                return_state=True,
                compute_dtype=jnp.dtype(r.ssd_compute_dtype))
            if mode == "prefill":
                new_cache = {"ssm": state.astype(jnp.float32),
                             "conv_x": cx, "conv_B": cb, "conv_C": cc}
            y = y.reshape(b, s, di)
        else:  # decode, s == 1
            x1, cx = ssm_lib.conv_decode_step(
                cache["conv_x"], x[:, 0], p["conv_x_w"], p["conv_x_b"])
            B1, cb = ssm_lib.conv_decode_step(
                cache["conv_B"], Bm[:, 0], p["conv_B_w"], p["conv_B_b"])
            C1, cc = ssm_lib.conv_decode_step(
                cache["conv_C"], Cm[:, 0], p["conv_C_w"], p["conv_C_b"])
            dt1 = jax.nn.softplus(dtr[:, 0].astype(jnp.float32)
                                  + p["dt_bias"].astype(jnp.float32))
            y1, state = ssm_lib.ssd_decode_step(
                cache["ssm"], x1.reshape(b, nh, pdim), dt1, A,
                B1.reshape(b, g, n), C1.reshape(b, g, n),
                p["ssm_D"].astype(jnp.float32))
            new_cache = {"ssm": state, "conv_x": cx, "conv_B": cb,
                         "conv_C": cc}
            y = y1.reshape(b, 1, di)

        gated = L.rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
        h = h + gated @ p["out_proj"].astype(dt_c)
        return r.constrain(h, r.act_btd(b)), new_cache

    # ----- layer stack -----

    def _transformer_layer(self, p, h, *, mode, cache, positions, mrope_pos,
                           cache_w):
        h, attn_cache = self.attention_sublayer(
            p, h, mode=mode, cache=cache, positions=positions,
            mrope_pos=mrope_pos, cache_w=cache_w)
        h, aux = self.mlp_sublayer(p, h)
        return h, aux, attn_cache

    def apply_layers(self, params, h, *, mode, caches=None, positions=None,
                     mrope_pos=None, cache_w: int = 0):
        """Run the layer stack.  Returns (h, aux_mean, new_caches)."""
        cfg, r = self.cfg, self.rules
        layers = params["layers"]

        if cfg.arch_type == "hybrid":
            return self._apply_hybrid(params, h, mode=mode, caches=caches,
                                      positions=positions, cache_w=cache_w)

        is_ssm = cfg.arch_type == "ssm"

        def body(carry, xs):
            h, aux = carry
            if mode == "decode" or (mode == "prefill" and is_ssm and
                                    caches is not None):
                p, layer_cache = xs
            else:
                p, layer_cache = xs, None
            if is_ssm:
                h, new_cache = self.mamba_sublayer(
                    p, h, mode=mode, cache=layer_cache)
                aux_i = jnp.zeros((), jnp.float32)
            else:
                h, aux_i, new_cache = self._transformer_layer(
                    p, h, mode=mode, cache=layer_cache, positions=positions,
                    mrope_pos=mrope_pos, cache_w=cache_w)
            if new_cache is None:
                new_cache = 0  # dummy ys
            return (h, aux + aux_i), new_cache

        if mode == "train" and r.remat:
            body = jax.checkpoint(body)

        if mode == "decode" or (mode == "prefill" and is_ssm
                                and caches is not None):
            xs = (layers, caches)
        else:
            xs = layers
        (h, aux), new_caches = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), xs)
        if mode == "train":
            new_caches = None
        return h, aux / cfg.num_layers, new_caches

    def _apply_hybrid(self, params, h, *, mode, caches, positions, cache_w):
        """Zamba2: Mamba2 backbone, ONE shared attention block applied every
        ``shared_attention_every`` layers (unrolled; 38 small layers)."""
        cfg = self.cfg
        every = cfg.shared_attention_every
        shared_p = params["shared_attn"]
        layers = params["layers"]
        n_inv = -(-cfg.num_layers // every)

        mamba_caches, attn_caches = (caches if caches is not None
                                     else (None, None))
        new_mamba, new_attn = [], []
        aux = jnp.zeros((), jnp.float32)

        # per-layer activation checkpointing: the hybrid stack is unrolled
        # (non-uniform shared-attention schedule), so the scan-body remat
        # doesn't apply — without this every [L, L] SSD intermediate of all
        # 38 layers is saved for the backward pass (§Perf p2 iteration 2)
        remat_train = mode == "train" and self.rules.remat

        def attn_layer(shared_p, h):
            return self.attention_sublayer(
                shared_p, h, mode=mode, cache=None,
                positions=positions, cache_w=cache_w)[0]

        def mamba_layer(p_i, h):
            return self.mamba_sublayer(p_i, h, mode=mode, cache=None)[0]

        if remat_train:
            attn_layer = jax.checkpoint(attn_layer)
            mamba_layer = jax.checkpoint(mamba_layer)

        for i in range(cfg.num_layers):
            if i % every == 0:
                inv = i // every
                a_cache = (jax.tree.map(lambda x: x[inv], attn_caches)
                           if attn_caches is not None else None)
                if remat_train:
                    h = attn_layer(shared_p, h)
                    nc = None
                else:
                    h, nc = self.attention_sublayer(
                        shared_p, h, mode=mode, cache=a_cache,
                        positions=positions, cache_w=cache_w)
                if nc is not None:
                    new_attn.append(nc)
            p_i = jax.tree.map(lambda x: x[i], layers)
            m_cache = (jax.tree.map(lambda x: x[i], mamba_caches)
                       if mamba_caches is not None else None)
            if remat_train:
                h = mamba_layer(p_i, h)
                nmc = None
            else:
                h, nmc = self.mamba_sublayer(p_i, h, mode=mode,
                                             cache=m_cache)
            if nmc is not None:
                new_mamba.append(nmc)
        del n_inv
        new_caches = None
        if new_mamba or new_attn:
            stack = lambda xs: jax.tree.map(
                lambda *a: jnp.stack(a), *xs) if xs else None
            new_caches = (stack(new_mamba), stack(new_attn))
        return h, aux, new_caches

    # ----- entry points -----

    def _maybe_cast_params(self, params):
        """§Perf knob: cast fp32 master params to bf16 before use, so the
        FSDP all-gathers at the layer boundaries move half the bytes.

        The with_sharding_constraint on each bf16 copy is load-bearing:
        without it GSPMD is free to hoist the convert AFTER the all-gather
        (gathering fp32 and converting locally), which keeps the collective
        bytes unchanged — measured in §Perf iteration 1.  Pinning the bf16
        copy to the param's own (sharded) spec forces a shard-local convert,
        so the gather (and its reduce-scatter transpose in the backward
        pass) moves bf16."""
        if self.rules.param_gather_dtype != "bfloat16":
            return params
        specs = self.param_specs()
        return jax.tree.map(
            lambda x, s: self.rules.constrain(x.astype(jnp.bfloat16), s)
            if x.dtype == jnp.float32 else x, params, specs)

    def _embed_inputs(self, params, batch):
        cfg, r = self.cfg, self.rules
        if "embeddings" in batch:  # vlm / audio frontend stub output
            h = batch["embeddings"].astype(self.compute_dtype)
        else:
            h = L.embed(batch["tokens"], params["embed"], self.compute_dtype)
        b = h.shape[0]
        return r.constrain(h, r.act_btd(b, self._act_seq(h.shape[1])))

    def _logits(self, params, h):
        cfg, r = self.cfg, self.rules
        b = h.shape[0]
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        table = (params["embed"].T if cfg.tie_embeddings
                 else params["lm_head"])
        logits = L.unembed(h, table)
        return r.constrain(logits, r.act_logits(b, cfg.vocab_size))

    def train_loss(self, params, batch):
        """batch: tokens|embeddings [B,S(,D)], labels [B,S],
        optional mrope_pos [B,S,3].  Returns (loss, metrics)."""
        cfg = self.cfg
        params = self._maybe_cast_params(params)
        h = self._embed_inputs(params, batch)
        b, s = h.shape[0], h.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        h, aux, _ = self.apply_layers(
            params, h, mode="train", positions=positions,
            mrope_pos=batch.get("mrope_pos"))
        logits = self._logits(params, h)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        # label logit without gathering across the vocab-sharded dim
        onehot_ll = jnp.sum(
            jnp.where(jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                      == labels[..., None], logits, 0.0), axis=-1)
        ce = jnp.mean(lse - onehot_ll)
        loss = ce
        if cfg.moe:
            loss = loss + cfg.moe.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    def prefill(self, params, batch, *, cache_len: int):
        """Fill caches for ``batch`` (tokens/embeddings of length S).
        Returns (last_logits [B, V], caches)."""
        cfg = self.cfg
        params = self._maybe_cast_params(params)
        h = self._embed_inputs(params, batch)
        b, s = h.shape[0], h.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        w = self.cache_window(cache_len)
        h, _, caches = self.apply_layers(
            params, h, mode="prefill", positions=positions,
            mrope_pos=batch.get("mrope_pos"), cache_w=w)
        logits = self._logits(params, h[:, -1:])
        return logits[:, 0], caches

    def decode_step(self, params, tokens, caches, pos):
        """One token: tokens [B, 1] ids; pos scalar int32 (abs position).
        Returns (logits [B, V], new caches)."""
        cfg, r = self.cfg, self.rules
        params = self._maybe_cast_params(params)
        h = L.embed(tokens, params["embed"], self.compute_dtype)
        b = h.shape[0]
        h = r.constrain(h, r.act_btd(b))
        mrope_pos = None
        if cfg.mrope:
            p3 = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1, 3))
            mrope_pos = p3
        h, _, new_caches = self.apply_layers(
            params, h, mode="decode", caches=caches, positions=pos,
            mrope_pos=mrope_pos)
        logits = self._logits(params, h)
        return logits[:, 0], new_caches

    # ----- caches -----

    def cache_window(self, cache_len: int) -> int:
        """Physical cache length: sliding window bounds it if set."""
        cfg = self.cfg
        if cfg.sliding_window and cfg.sliding_window < cache_len:
            return cfg.sliding_window
        return cache_len

    def _attn_cache_leaf(self, batch: int, w: int):
        cfg = self.cfg
        return {
            "k": (batch, w, cfg.num_kv_heads, cfg.head_dim),
            "v": (batch, w, cfg.num_kv_heads, cfg.head_dim),
            "slot_pos": (w,),
        }

    def _ssm_cache_leaf(self, batch: int):
        s = self.cfg.ssm
        di = s.expand * self.cfg.d_model
        nh = di // s.head_dim
        gn = s.ngroups * s.state_dim
        return {
            "ssm": (batch, nh, s.head_dim, s.state_dim),
            "conv_x": (batch, s.conv_width - 1, di),
            "conv_B": (batch, s.conv_width - 1, gn),
            "conv_C": (batch, s.conv_width - 1, gn),
        }

    def cache_shapes(self, batch: int, cache_len: int):
        """Shapes pytree (tuples) for the decode cache."""
        cfg = self.cfg
        w = self.cache_window(cache_len)
        ln = cfg.num_layers
        stack = lambda d: {k: (ln,) + v for k, v in d.items()}
        if cfg.arch_type == "ssm":
            return stack(self._ssm_cache_leaf(batch))
        if cfg.arch_type == "hybrid":
            n_inv = -(-ln // cfg.shared_attention_every)
            attn_leaf = self._attn_cache_leaf(batch, w)
            return (stack(self._ssm_cache_leaf(batch)),
                    {k: (n_inv,) + v for k, v in attn_leaf.items()})
        return stack(self._attn_cache_leaf(batch, w))

    def cache_specs(self, batch: int, cache_len: int):
        """PartitionSpec pytree congruent with cache_shapes."""
        cfg, r = self.cfg, self.rules
        dp = r.dp(batch)
        tp = r.model_axis
        cax = r.cache_axes
        attn_spec = {"k": P(None, dp, cax, None, None),
                     "v": P(None, dp, cax, None, None),
                     "slot_pos": P(None, cax)}
        ssm_spec = {"ssm": P(None, dp, tp, None, None),
                    "conv_x": P(None, dp, None, tp),
                    "conv_B": P(None, dp, None, None),
                    "conv_C": P(None, dp, None, None)}
        if cfg.arch_type == "ssm":
            return ssm_spec
        if cfg.arch_type == "hybrid":
            return (ssm_spec, attn_spec)
        return attn_spec

    def cache_dtypes(self, batch: int, cache_len: int):
        cdt = self.compute_dtype
        def leaf_dtype(name):
            if name == "slot_pos":
                return jnp.int32
            if name == "ssm":
                return jnp.float32
            return cdt
        shapes = self.cache_shapes(batch, cache_len)
        return jax.tree.map_with_path(
            lambda path, shape: leaf_dtype(path[-1].key), shapes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(i, int) for i in x))

    def init_cache(self, batch: int, cache_len: int):
        shapes = self.cache_shapes(batch, cache_len)
        dtypes = self.cache_dtypes(batch, cache_len)

        def mk(shape, dt):
            if dt == jnp.int32:
                return jnp.full(shape, -1, jnp.int32)
            return jnp.zeros(shape, dt)

        return jax.tree.map(
            mk, shapes, dtypes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(i, int) for i in x))
