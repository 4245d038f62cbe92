"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

TPU adaptation (DESIGN.md §2): the chunk dimension is the innermost grid
axis, so the [P, N] inter-chunk state lives in VMEM scratch and is carried
sequentially across chunk iterations — the TPU-native replacement for the
GPU kernel's warp-level state exchange.  Within a chunk the computation is
three MXU matmuls (C@B^T, P@x, x^T@B) over [L, N]/[L, P] tiles with the
decay factors applied as VPU elementwise ops.

Grid: (B*H, n_chunks).  B/C are shared across head groups via the BlockSpec
index map (no materialized repeat).

Validated on CPU (interpret mode) against the naive recurrence oracle
``repro.kernels.ref.ssd_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                st_ref, state_scr, *, chunk: int, seq_len: int, heads: int):
    hi = pl.program_id(0) % heads
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    # dt arrives twice, as a column [L, 1] and as a row [1, L]: the chip's
    # vector unit reduces along either axis but cannot cheaply transpose a
    # one-wide vector, and the decay matrix needs the cumulative sum both
    # ways round.
    x = x_ref[0].astype(jnp.float32)        # [L, P]
    dt = dt_ref[0].astype(jnp.float32)      # [L, 1]
    dtr = dtr_ref[0].astype(jnp.float32)    # [1, L]
    # EXACT pad masking (the same discipline flash_attention applies
    # with its `kpos < seq_k` mask): zero dt at padded positions, so a
    # padded step contributes nothing to the intra-chunk quadratic
    # (pmat's column weight is dt_j), nothing to the state update
    # (w ~ dt), and leaves the cumulative decay flat — the carried
    # state and final_state come out bit-identical to the unpadded
    # recurrence for ANY chunk the tuner may pick, instead of drifting
    # by an epsilon that scales with the pad count.
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dt = jnp.where(pos < seq_len, dt, 0.0)
    posr = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    dtr = jnp.where(posr < seq_len, dtr, 0.0)
    a = a_ref[hi, 0]                         # scalar (negative)
    bm = b_ref[0].astype(jnp.float32)       # [L, N]
    cm = c_ref[0].astype(jnp.float32)       # [L, N]
    dD = d_ref[hi, 0]                        # scalar

    # inclusive cumulative sums of dA = dt * a, as a column and as a row
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    cs = jnp.sum(jnp.where(causal, dtr * a, 0.0), axis=1,
                 keepdims=True)              # [L, 1]
    csr = jnp.sum(jnp.where(causal, 0.0, dt * a), axis=0,
                  keepdims=True) + dtr * a   # [1, L]
    cs_last = jnp.sum(dtr * a, axis=1, keepdims=True)  # [1, 1]

    # ---- intra-chunk (masked decay-weighted quadratic) ----
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [L, L] = C_i . B_j
    decay = jnp.where(causal, jnp.exp(cs - csr), 0.0)
    pmat = scores * decay * dtr              # weight column j by dt_j
    y = jax.lax.dot_general(pmat, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, P]

    # ---- inter-chunk contribution from the carried state ----
    st = state_scr[...]                      # [P, N]
    y += jax.lax.dot_general(
        cm, st, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cs)

    # ---- skip connection ----
    y += x * dD
    y_ref[0] = y.astype(y_ref.dtype)

    # ---- state update ----
    w = jnp.exp(cs_last - cs) * dt           # [L, 1]
    st_add = jax.lax.dot_general(
        x * w, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [P, N]
    state_scr[...] = st * jnp.exp(cs_last) + st_add

    @pl.when(ci == nc - 1)
    def _emit_state():
        st_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_kernel(x, dt, A, B, C, D, *, chunk: int = 256,
                       interpret: bool = False):
    """x [B, S, H, P]; dt [B, S, H] (>0); A [H] (<0); B/C [B, S, G, N];
    D [H].  Returns (y [B, S, H, P], final_state [B, H, P, N])."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    # clamp like flash_attention clamps block_q/block_k: a tuned config
    # from a larger shape-bucket (or a corrupt profile's nonsense value)
    # must degrade to a legal launch, never break a short-sequence call
    chunk = max(1, min(chunk, s))
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad value is irrelevant: the kernel hard-masks dt by position
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk

    xf = x.transpose(0, 2, 1, 3).reshape(b * h, sp, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, sp, 1)
    dtr = dtf.reshape(b * h, 1, sp)
    bf = B.transpose(0, 2, 1, 3).reshape(b * g, sp, n)
    cf = C.transpose(0, 2, 1, 3).reshape(b * g, sp, n)
    af = A.reshape(h, 1).astype(jnp.float32)
    df = D.reshape(h, 1).astype(jnp.float32)

    def xmap(bh, ci):
        return (bh, ci, 0)

    def bcmap(bh, ci):
        bi, hi = bh // h, bh % h
        return (bi * g + hi // hg, ci, 0)

    def rowmap(bh, ci):
        return (bh, 0, ci)

    def stmap(bh, ci):
        return (bh, 0, 0)

    # A and D: [h, 1] per-head scalars, held whole in scalar memory and
    # indexed by the grid's head (a (1, 1) VMEM block of an [h, 1] array
    # breaks the TPU's (8, 128) tiling rule)
    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, seq_len=s, heads=h),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), xmap),
            pl.BlockSpec((1, chunk, 1), xmap),
            pl.BlockSpec((1, 1, chunk), rowmap),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), bcmap),
            pl.BlockSpec((1, chunk, n), bcmap),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), xmap),
            pl.BlockSpec((1, p, n), stmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sp, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, dtr, af, bf, cf, df)

    y = y.reshape(b, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    st = st.reshape(b, h, p, n)
    return y, st
