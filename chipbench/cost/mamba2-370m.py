"""FLOPs of mamba2-370m per token, from its published shapes and the
Mamba-2 block's equations (arXiv:2405.21060).

Counted: every matrix product the model needs, the SSD quadratic form over
the causal half of each chunk (a token sees (chunk + 1) / 2 positions of
its chunk on average), the chunk states and their read-out, the recurrence
between chunks, and the output head.  Not counted: the embedding lookup,
norms, convolutions' adds and other elementwise work, and any
recomputation (the backward pass is twice the forward pass).
"""

from __future__ import annotations


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    s = m["ssm"]
    d, v, layers = m["d_model"], m["vocab_size"], m["num_layers"]
    di = s["expand"] * d
    h, p, n, g = di // s["head_dim"], s["head_dim"], s["state_dim"], s["ngroups"]
    l = min(s["chunk_size"], seq_len)
    proj = 2 * d * (2 * di + 2 * g * n + h) + 2 * di * d
    conv = 2 * s["conv_width"] * (di + 2 * g * n)
    ssd = (g * n * (l + 1)          # C_i . B_j over the causal half
           + h * p * (l + 1)        # (CB * decay) x over the causal half
           + 2 * h * p * n          # chunk states
           + 2 * h * p * n          # states read out by C
           + 2 * h * p * n / l)     # recurrence between chunks
    return layers * (proj + conv + ssd) + 2 * d * v


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3 * forward_flops_per_token(m, seq_len)
