"""Plain float32 Mamba-2 language model, written from arXiv:2405.21060.

Each block is pre-norm and residual (the paper's Mamba-2 block, Fig. 6):

    a = RMSNorm(h)
    z, x, B, C, dt = a W_z, a W_x, a W_B, a W_C, a W_dt      (the in-projection)
    x, B, C = SiLU(depthwise causal conv_w(x | B | C) + bias)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    y = SSD(x, dt, A, B, C) + D x                            (Listing 1, chunked)
    h = h + RMSNorm(y * SiLU(z)) W_out

and the model is ``logits = RMSNorm(h) W_head`` over token embeddings, with
the mean cross-entropy as the loss.  Everything is float32 and every
product runs at ``Precision.HIGHEST`` (or at the control's precision, see
``precision.py``).  The layers are a ``lax.scan`` with each block
rematerialised and the loss taken one batch row at a time, so a full-width
gradient fits one chip beside the optimizer state.

The parameter layout (names and shapes) is the one the system under test
takes, so that one set of seeded weights drives both; the initial values
are the paper's (A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1]).
With ``tie_embeddings`` the head is the embedding's transpose, as published.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.precision import F32, Dots


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def dims(m: dict) -> dict:
    s = m["ssm"]
    d = m["d_model"]
    di = s["expand"] * d
    return {"d": d, "di": di, "h": di // s["head_dim"], "p": s["head_dim"],
            "g": s["ngroups"], "n": s["state_dim"], "w": s["conv_width"],
            "chunk": s["chunk_size"], "L": m["num_layers"],
            "v": m["vocab_size"], "eps": m["norm_eps"]}


def block_schema(m: dict, layers: int) -> dict:
    """{leaf: (shape, init)} of ``layers`` stacked Mamba-2 blocks."""
    k = dims(m)
    d, di, h, gn, w = k["d"], k["di"], k["h"], k["g"] * k["n"], k["w"]
    out_scale = 0.02 / math.sqrt(2 * m["num_layers"])
    pre = (layers,)
    return {
        "norm": (pre + (d,), ("ones",)),
        "z_proj": (pre + (d, di), ("normal", 0.02)),
        "x_proj": (pre + (d, di), ("normal", 0.02)),
        "B_proj": (pre + (d, gn), ("normal", 0.02)),
        "C_proj": (pre + (d, gn), ("normal", 0.02)),
        "dt_proj": (pre + (d, h), ("normal", 0.02)),
        "conv_x_w": (pre + (w, di), ("normal", 0.2)),
        "conv_x_b": (pre + (di,), ("zeros",)),
        "conv_B_w": (pre + (w, gn), ("normal", 0.2)),
        "conv_B_b": (pre + (gn,), ("zeros",)),
        "conv_C_w": (pre + (w, gn), ("normal", 0.2)),
        "conv_C_b": (pre + (gn,), ("zeros",)),
        "A_log": (pre + (h,), ("a_log",)),
        "ssm_D": (pre + (h,), ("ones",)),
        "dt_bias": (pre + (h,), ("dt_bias",)),
        "gate_norm": (pre + (di,), ("ones",)),
        "out_proj": (pre + (di, d), ("normal", out_scale)),
    }


def schema(m: dict) -> dict:
    d, v = m["d_model"], m["vocab_size"]
    out = {"embed": ((v, d), ("normal", 0.02)),
           "final_norm": ((d,), ("ones",)),
           "layers": block_schema(m, m["num_layers"])}
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, v), ("normal", 0.02))
    return out


def init_leaf(key, shape, rule):
    kind = rule[0]
    if kind == "normal":
        return rule[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    raise ValueError(kind)


def init_from_schema(sch: dict, key) -> dict:
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    flat, tree = jax.tree.flatten(sch, is_leaf=is_leaf)
    keys = jax.random.split(key, len(flat))
    return jax.tree.unflatten(
        tree, [init_leaf(k, shape, rule) for k, (shape, rule)
               in zip(keys, flat)])


def init(m: dict, key) -> dict:
    return init_from_schema(schema(m), key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence: x [B, S, C],
    w [W, C] (tap W-1 on the current position), b [C]."""
    width, ch = w.shape
    y = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(width - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=ch,
        precision=jax.lax.Precision.HIGHEST)
    return y + b


def segsum(x):
    """out[..., i, j] = sum(x[..., j+1 : i+1]) for i >= j, -inf above."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    diff = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), diff, -jnp.inf)


def ssd(x, dt, A, B, C, chunk, dots: Dots = F32, init_state=None):
    """Listing 1 of the paper (ssd_minimal_discrete) in float32.

    x [b, s, h, p], dt [b, s, h], A [h], B/C [b, s, g, n].  Returns
    y [b, s, h, p] (without the D skip) and the final state [b, h, p, n]."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    X = x * dt[..., None]
    dA = dt * A
    Bh = jnp.repeat(B, h // g, axis=2)
    Ch = jnp.repeat(C, h // g, axis=2)
    if pad:
        padw = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        X, dA, Bh, Ch = padw(X), padw(dA), padw(Bh), padw(Ch)
    c = X.shape[1] // chunk
    X = X.reshape(b, c, chunk, h, p)
    Bh = Bh.reshape(b, c, chunk, h, n)
    Ch = Ch.reshape(b, c, chunk, h, n)
    dA = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)      # b h c l
    cs = jnp.cumsum(dA, -1)
    # 1. the diagonal (within-chunk) blocks
    Lm = jnp.exp(segsum(dA))
    cb = dots.einsum("bclhn,bcshn->bhcls", Ch, Bh)
    y_diag = dots.einsum("bhcls,bcshp->bclhp", cb * Lm, X)
    # 2. each chunk's end state
    decay = jnp.exp(cs[..., -1:] - cs)
    states = dots.einsum("bclhn,bclhp->bchpn", Bh,
                         X * decay.transpose(0, 2, 3, 1)[..., None])
    # 3. the recurrence between chunks, as one product
    s0 = (jnp.zeros_like(states[:, :1]) if init_state is None
          else init_state[:, None].astype(jnp.float32))
    states = jnp.concatenate([s0, states], axis=1)
    chunk_decay = jnp.exp(segsum(jnp.pad(cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    new = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                     precision=jax.lax.Precision.HIGHEST)
    states, final = new[:, :-1], new[:, -1]
    # 4. states to outputs
    y_off = dots.einsum("bclhn,bchpn->bclhp", Ch, states) \
        * jnp.exp(cs).transpose(0, 2, 3, 1)[..., None]
    y = (y_diag + y_off).reshape(b, c * chunk, h, p)[:, :s]
    return y, final


def block(m: dict, p: dict, h, dots: Dots = F32):
    """One Mamba-2 block over a whole sequence: h [B, S, D] -> h'."""
    k = dims(m)
    bsz, s, _ = h.shape
    a = rms_norm(h, p["norm"], k["eps"])
    z = dots.einsum("bsd,de->bse", a, p["z_proj"])
    x = dots.einsum("bsd,de->bse", a, p["x_proj"])
    B = dots.einsum("bsd,de->bse", a, p["B_proj"])
    C = dots.einsum("bsd,de->bse", a, p["C_proj"])
    dt = dots.einsum("bsd,de->bse", a, p["dt_proj"])
    x = jax.nn.silu(causal_conv(x, p["conv_x_w"], p["conv_x_b"]))
    B = jax.nn.silu(causal_conv(B, p["conv_B_w"], p["conv_B_b"]))
    C = jax.nn.silu(causal_conv(C, p["conv_C_w"], p["conv_C_b"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(bsz, s, k["h"], k["p"])
    y, _ = ssd(xh, dt, A, B.reshape(bsz, s, k["g"], k["n"]),
               C.reshape(bsz, s, k["g"], k["n"]), k["chunk"], dots)
    y = y + xh * p["ssm_D"][None, None, :, None]
    y = rms_norm(y.reshape(bsz, s, k["di"]) * jax.nn.silu(z),
                 p["gate_norm"], k["eps"])
    return h + dots.einsum("bse,ed->bsd", y, p["out_proj"])


def hidden(m: dict, params: dict, tokens, dots: Dots = F32):
    """Final hidden states [B, S, D] (before the last norm)."""
    h = params["embed"][tokens].astype(jnp.float32)

    def body(h, p):
        return jax.checkpoint(lambda h, p: block(m, p, h, dots))(h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return h


def head(m: dict, params: dict):
    return params["embed"].T if m["tie_embeddings"] else params["lm_head"]


def logits(m: dict, params: dict, h, dots: Dots = F32):
    h = rms_norm(h, params["final_norm"], m["norm_eps"])
    return dots.einsum("bsd,dv->bsv", h, head(m, params))


def loss(m: dict, params: dict, tokens, labels, dots: Dots = F32):
    """Mean next-token cross-entropy over every position of the batch."""
    h = hidden(m, params, tokens, dots)

    @jax.checkpoint
    def row_nll(hl):
        h_row, lab = hl
        lg = logits(m, params, h_row[None], dots)[0]
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(row_nll, (h, labels))) / labels.size
