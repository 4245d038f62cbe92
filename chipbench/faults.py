"""What a training cell's comparison has to catch, planted under its timed
path in place of the program's step:

  control          the plain reference with its products in float8
                   (``precision.FP8``), the precision below the
                   configuration's bfloat16, in the program's place;
  state_unchanged  a step that returns its parameters and optimizer state
                   unchanged;
  half_batch       half of each batch left out, the mean taken over the
                   rest.

``planted(fault, ctx)`` patches the program for the length of a ``with``
block, so that the cell's own driver runs it and ``harness.result``
decides ``correct`` against ``limits/<workload>.json`` as in any run.
``tools/control_train.py`` reads them at the cell's size on the chip; the
CPU tests at the tiny size.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax

from chipbench.reference import adamw
from chipbench.reference.precision import FP8

FAULTS = ("control", "state_unchanged", "half_batch")


def reference_step(ref, m: dict, hp: dict, dots):
    """The reference's loss, gradient and AdamW update at ``dots``, with
    the program's step signature (params, opt, batch) -> (params', opt',
    metrics)."""

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, batch):
        loss, g = jax.value_and_grad(lambda p: ref.loss(
            m, p, batch["tokens"], batch["labels"], dots))(params)
        params, mu, nu, _ = adamw.update(params, g, opt["mu"], opt["nu"],
                                         opt["step"], hp)
        return params, {"mu": mu, "nu": nu, "step": opt["step"] + 1}, \
            {"loss": loss, "grad_norm": adamw.global_norm(g)}

    return step


@contextlib.contextmanager
def planted(fault: str, ctx):
    from repro.models.model import Model
    from repro.train import loop, step

    real_step, real_loss = step.jit_train_step, Model.train_loss
    if fault == "control":
        fp8 = reference_step(ctx.reference, ctx.config["model"],
                             ctx.config["optimizer"], FP8)
        targets = [(step, "jit_train_step", lambda *a, **kw: fp8),
                   (loop, "jit_train_step", lambda *a, **kw: fp8)]
    elif fault == "state_unchanged":
        def unchanged(model, opt_cfg, batch, *a, **kw):
            fn = real_step(model, opt_cfg, batch, *a, **dict(kw,
                                                             donate=False))
            return jax.jit(lambda p, o, b: (p, o, fn(p, o, b)[2]))
        targets = [(step, "jit_train_step", unchanged),
                   (loop, "jit_train_step", unchanged)]
    elif fault == "half_batch":
        def half(self, params, batch):
            return real_loss(self, params, {k: v[:v.shape[0] // 2]
                                            for k, v in batch.items()})
        targets = [(Model, "train_loss", half)]
    else:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in targets]
    for obj, name, value in targets:
        setattr(obj, name, value)
    try:
        yield
    finally:
        for obj, name, value in saved:
            setattr(obj, name, value)
