"""Plain AdamW (Loshchilov & Hutter, arXiv:1711.05101) with the gradient
clipped to a global norm first, as the configuration states it: decoupled
weight decay on every parameter, bias-corrected moments."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def clip(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def update(params, grads, mu, nu, count, hp: dict):
    """One step.  ``count`` is the number of steps taken before this one.
    Returns (params', mu', nu', clipped grads)."""
    g = clip(grads, hp["grad_clip"])
    t = count + 1
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1 = 1.0 - b1 ** jnp.float32(t)
    c2 = 1.0 - b2 ** jnp.float32(t)

    def new(p, m, v):
        upd = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
        return p - hp["lr"] * (upd + hp["weight_decay"] * p)

    return jax.tree.map(new, params, mu, nu), mu, nu, g
