"""Matrix-product precision of the plain references.

``F32`` is the reference itself: float32 operands, float32 accumulation,
``Precision.HIGHEST`` (on a TPU a float32 product at default precision
runs as one bfloat16 pass).  ``FP8`` is the control, the usual scaled
float8 recipe in both passes: in the forward pass each operand is rounded
to float8 e4m3 with a per-tensor scale taken from its largest magnitude,
and in the backward pass the gradient arriving at each product is rounded
to float8 e5m2 the same way, so that the products of the backward pass
take float8 operands too; the products themselves accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scaled(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_operand(x):
    """e4m3 forward; the gradient passes straight through."""
    return _scaled(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None),
                    lambda _, g: (g,))


@jax.custom_vjp
def _fp8_grad(y):
    """Identity forward; the gradient arriving here is rounded to e5m2."""
    return y


_fp8_grad.defvjp(lambda y: (y, None),
                 lambda _, g: (_scaled(g, jnp.float8_e5m2),))


class Dots:
    """``einsum`` at one operand precision: ``"f32"`` or ``"fp8"``."""

    def __init__(self, kind: str = "f32"):
        if kind not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {kind!r}")
        self.kind = kind

    def _op(self, x):
        x = x.astype(jnp.float32)
        return _fp8_operand(x) if self.kind == "fp8" else x

    def einsum(self, spec: str, *ops):
        y = jnp.einsum(spec, *(self._op(o) for o in ops), precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        return _fp8_grad(y) if self.kind == "fp8" else y


F32 = Dots("f32")
FP8 = Dots("fp8")
