"""Unit tests for model building blocks: chunked attention vs oracle,
RoPE/M-RoPE, MoE dispatch math, SSD decode-vs-chunked consistency, caches."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.kernels.ref import attention_reference
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import ssm as ssm_lib
from repro.models.moe import (capacity_for, moe_block_local, router_topk)


class TestChunkedAttention:
    @pytest.mark.parametrize("sq,window,qc,kc", [
        (256, 0, 64, 64), (256, 0, 128, 64), (200, 0, 64, 64),
        (256, 64, 64, 64), (256, 100, 128, 128)])
    def test_vs_reference(self, sq, window, qc, kc):
        b, hq, hkv, d = 2, 4, 2, 32
        ks = jax.random.split(jax.random.key(sq + window), 3)
        q = jax.random.normal(ks[0], (b, sq, hq, d))
        k = jax.random.normal(ks[1], (b, sq, hkv, d))
        v = jax.random.normal(ks[2], (b, sq, hkv, d))
        pos = jnp.arange(sq, dtype=jnp.int32)
        out = attn.chunked_attention(q, k, v, pos, pos, window=window,
                                     q_chunk=qc, k_chunk=kc)
        ref = attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            window=window).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_block_skip_equals_no_skip(self):
        b, s, h, d = 1, 256, 2, 32
        ks = jax.random.split(jax.random.key(5), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        pos = jnp.arange(s, dtype=jnp.int32)
        a = attn.chunked_attention(q, k, v, pos, pos, q_chunk=64, k_chunk=64,
                                   skip_masked_blocks=True)
        b_ = attn.chunked_attention(q, k, v, pos, pos, q_chunk=64,
                                    k_chunk=64, skip_masked_blocks=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)

    def test_decode_attention_matches_reference(self):
        b, hq, hkv, d, w = 2, 4, 2, 32, 64
        ks = jax.random.split(jax.random.key(1), 3)
        q1 = jax.random.normal(ks[0], (b, 1, hq, d))
        kc = jax.random.normal(ks[1], (b, w, hkv, d))
        vc = jax.random.normal(ks[2], (b, w, hkv, d))
        # cache holds positions 0..39 (slots beyond are empty)
        slot_pos = jnp.where(jnp.arange(w) < 40, jnp.arange(w), -1)
        out = attn.decode_attention(q1, kc, vc, slot_pos, jnp.int32(40))
        # causal=False ok: all 40 slots <= pos 40 are visible
        ref2 = attention_reference(
            q1.transpose(0, 2, 1, 3), kc[:, :40].transpose(0, 2, 1, 3),
            vc[:, :40].transpose(0, 2, 1, 3), causal=False
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref2),
                                   atol=2e-5, rtol=2e-5)

    def test_rolling_cache_append(self):
        cache = attn.init_cache(1, 4, 2, 8, jnp.float32)
        for pos in range(6):
            k1 = jnp.full((1, 1, 2, 8), float(pos))
            cache = attn.cache_append(cache, k1, k1, jnp.int32(pos))
        # window 4: slots hold positions 4,5,2,3 (pos % 4)
        np.testing.assert_array_equal(np.asarray(cache["slot_pos"]),
                                      [4, 5, 2, 3])
        assert float(cache["k"][0, 1, 0, 0]) == 5.0


class TestRoPE:
    def test_rotation_preserves_norm(self):
        x = jax.random.normal(jax.random.key(0), (2, 16, 4, 64))
        pos = jnp.arange(16)
        y = L.apply_rope(x, pos, 1e4)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)

    def test_relative_property(self):
        """<rope(q,p1), rope(k,p2)> depends only on p1-p2."""
        d = 64
        q = jax.random.normal(jax.random.key(1), (1, 1, 1, d))
        k = jax.random.normal(jax.random.key(2), (1, 1, 1, d))

        def dot(p1, p2):
            qr = L.apply_rope(q, jnp.array([p1]), 1e4)
            kr = L.apply_rope(k, jnp.array([p2]), 1e4)
            return float(jnp.sum(qr * kr))
        assert abs(dot(5, 3) - dot(105, 103)) < 1e-4

    def test_mrope_equals_rope_for_text(self):
        """With all three position streams equal, M-RoPE == 1-D RoPE."""
        b, s, h, d = 1, 8, 2, 64
        x = jax.random.normal(jax.random.key(3), (b, s, h, d))
        pos = jnp.arange(s, dtype=jnp.int32)
        p3 = jnp.broadcast_to(pos[None, :, None], (b, s, 3))
        y1 = L.apply_rope(x, pos, 1e4)
        y2 = L.apply_mrope(x, p3, 1e4, (16, 8, 8))
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-5)

    def test_mrope_sections_differ_for_spatial(self):
        b, s, h, d = 1, 8, 1, 64
        x = jax.random.normal(jax.random.key(4), (b, s, h, d))
        pos = jnp.arange(s, dtype=jnp.int32)
        p_text = jnp.broadcast_to(pos[None, :, None], (b, s, 3))
        p_img = p_text.at[:, :, 1].set(0)  # different height stream
        y1 = L.apply_mrope(x, p_text, 1e4, (16, 8, 8))
        y2 = L.apply_mrope(x, p_img, 1e4, (16, 8, 8))
        assert not np.allclose(np.asarray(y1), np.asarray(y2))


class TestMoE:
    def _run_local(self, x, rw, wg, wu, wd, moe):
        """moe_block_local needs mesh axes: run under a 1-device shard_map."""
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        fn = jax.shard_map(
            lambda *a: moe_block_local(*a, moe=moe, model_axis="model",
                                       data_axes=("data",)),
            mesh=mesh, in_specs=(P(), P(), P(), P(), P()),
            out_specs=(P(), P()), check_vma=False)
        return fn(x, rw, wg, wu, wd)

    def test_matches_dense_loop_when_capacity_ample(self):
        """With no drops, sort-based dispatch == explicit per-token loop."""
        t, dm, e, k, f = 32, 16, 4, 2, 32
        moe = MoEConfig(num_experts=e, experts_per_token=k,
                        capacity_factor=8.0)
        ks = jax.random.split(jax.random.key(0), 5)
        x = jax.random.normal(ks[0], (t, dm))
        rw = jax.random.normal(ks[1], (dm, e)) * 0.5
        wg = jax.random.normal(ks[2], (e, dm, f)) * 0.1
        wu = jax.random.normal(ks[3], (e, dm, f)) * 0.1
        wd = jax.random.normal(ks[4], (e, f, dm)) * 0.1
        y, aux = self._run_local(x, rw, wg, wu, wd, moe)

        probs, gate, idx = router_topk(x.astype(jnp.float32), rw, k)
        y_ref = np.zeros((t, dm), np.float32)
        for ti in range(t):
            for kk in range(k):
                ei = int(idx[ti, kk])
                h = (jax.nn.silu(x[ti] @ wg[ei]) * (x[ti] @ wu[ei])) @ wd[ei]
                y_ref[ti] += float(gate[ti, kk]) * np.asarray(h)
        np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-4,
                                   rtol=1e-4)

    def test_capacity_drops_tokens(self):
        t, dm, e, k, f = 64, 8, 2, 1, 8
        moe_tight = MoEConfig(e, k, capacity_factor=0.25)
        ks = jax.random.split(jax.random.key(1), 5)
        x = jax.random.normal(ks[0], (t, dm))
        rw = jnp.zeros((dm, e)).at[0, 0].set(10.0)
        wg = jnp.ones((e, dm, f)) * 0.1
        wu = jnp.ones((e, dm, f)) * 0.1
        wd = jnp.ones((e, f, dm)) * 0.1
        y, _ = self._run_local(x, rw, wg, wu, wd, moe_tight)
        # capacity = ceil(64*1/2*0.25) = 8 per expert -> at most e*cap
        # tokens survive; everything else was dropped (= zero rows)
        zero_rows = np.sum(~np.any(np.asarray(y), axis=1))
        assert zero_rows >= t - e * capacity_for(t, moe_tight)

    def test_aux_loss_uniform_router_is_one(self):
        t, e = 1024, 8
        probs = jnp.full((t, e), 1.0 / e)
        idx = jnp.stack([jnp.arange(t) % e, (jnp.arange(t) + 1) % e], 1)
        from repro.models.moe import load_balance_aux
        aux = load_balance_aux(probs, idx, e)
        assert abs(float(aux) - 1.0) < 1e-5


class TestSSM:
    def test_chunked_matches_stepwise_decode(self):
        """Prefill with ssd_chunked then decode steps == full recurrence."""
        b, s, h, p, g, n = 1, 48, 2, 16, 1, 8
        ks = jax.random.split(jax.random.key(0), 5)
        x = jax.random.normal(ks[0], (b, s + 4, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s + 4, h)))
        A = -jnp.exp(jax.random.uniform(ks[2], (h,)))
        B = jax.random.normal(ks[3], (b, s + 4, g, n)) * 0.5
        C = jax.random.normal(ks[4], (b, s + 4, g, n)) * 0.5
        D = jnp.ones((h,))

        y_all, _ = ssm_lib.ssd_reference(x, dt, A, B, C, D)
        _, state = ssm_lib.ssd_chunked(x[:, :s], dt[:, :s], A, B[:, :s],
                                       C[:, :s], D, chunk=16,
                                       return_state=True)
        for t in range(s, s + 4):
            y1, state = ssm_lib.ssd_decode_step(
                state, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
            np.testing.assert_allclose(np.asarray(y1),
                                       np.asarray(y_all[:, t]),
                                       atol=5e-4, rtol=5e-4)

    def test_causal_conv_matches_decode_steps(self):
        b, s, ch, w = 2, 16, 8, 4
        ks = jax.random.split(jax.random.key(1), 3)
        x = jax.random.normal(ks[0], (b, s, ch))
        wgt = jax.random.normal(ks[1], (w, ch)) * 0.3
        bias = jax.random.normal(ks[2], (ch,)) * 0.1
        y_full, tail = ssm_lib.causal_conv(x, wgt, bias)
        state = jnp.zeros((b, w - 1, ch))
        for t in range(s):
            y1, state = ssm_lib.conv_decode_step(state, x[:, t], wgt, bias)
            np.testing.assert_allclose(np.asarray(y1),
                                       np.asarray(y_full[:, t]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(state), np.asarray(tail),
                                   atol=1e-6)

    def test_segsum(self):
        dA = jnp.array([[1.0, 2.0, 3.0]])
        out = ssm_lib.segsum(dA)[0]
        assert out[0, 0] == 0.0
        assert out[1, 0] == 2.0          # sum of dA[1]
        assert out[2, 0] == 5.0          # dA[1]+dA[2]
        assert out[0, 1] == -jnp.inf
