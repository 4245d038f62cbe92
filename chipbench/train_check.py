"""The comparison that decides ``correct`` in the training cells.

Each number is taken against the plain reference run from the same
seeded weights and batches:

  loss_gap        the largest |loss - reference loss| / |reference loss|
                  over the steps compared; loss_gap_first, the first step's;
  grad_gap        the first step's gradient as the optimizer got it (read
                  back from the first moment after that step), per leaf:
                  |norm - reference norm| / max(reference norm, median
                  leaf's), the worst leaf; grad_gap_median, the median leaf;
  change_gap      the same of the parameters' change over the steps
                  compared, over the leaves whose reference gradient is at
                  least a thousandth of the median leaf's (a leaf with no
                  gradient moves under AdamW by round-off alone);
                  change_gap_median, the median leaf.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import leaf_gaps
from chipbench.reference import adamw

GRAD_FLOOR = 1e-3


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


@partial(jax.jit, static_argnums=(2,))
def _moment_grad_norms(mu_new, mu_old, b1):
    """Norm of g from mu' = b1 mu + (1 - b1) g, per leaf."""
    return jax.tree.map(
        lambda n, o: jnp.sqrt(jnp.sum(jnp.square(
            (n.astype(jnp.float32) - b1 * o.astype(jnp.float32))
            / (1.0 - b1)))), mu_new, mu_old)


def norms(tree) -> dict:
    return {k: float(v) for k, v in flat(_norms(tree)).items()}


def diff_norms(a, b) -> dict:
    return {k: float(v) for k, v in flat(_diff_norms(a, b)).items()}


def moment_grad_norms(mu_new, mu_old, b1: float) -> dict:
    return {k: float(v) for k, v in
            flat(_moment_grad_norms(mu_new, mu_old, float(b1))).items()}


def reference_steps(ref, m: dict, hp: dict, make_state, batches, dots,
                    count0: int = 0) -> dict:
    """Run the reference through ``batches`` from the state
    ``make_state()`` gives (params, mu, nu), on the chip, one jitted step
    at a time.  Returns the losses, the first step's clipped-gradient
    norms and the per-leaf norms of the parameters' change."""

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, tok, lab):
        loss, g = jax.value_and_grad(
            lambda p: ref.loss(m, p, tok, lab, dots))(params)
        params, mu, nu, gc = adamw.update(params, g, mu, nu, count, hp)
        return loss, params, mu, nu, jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), gc)

    params, mu, nu = make_state()
    losses, grad = [], None
    for i, (tok, lab) in enumerate(batches):
        loss, params, mu, nu, gn = step(params, mu, nu, jnp.int32(count0 + i),
                                        jnp.asarray(tok), jnp.asarray(lab))
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(v) for k, v in flat(gn).items()}
    del mu, nu
    start = make_state()[0]
    change = diff_norms(params, start)
    return {"losses": losses, "grad": grad, "change": change}


def readings(prog: dict, ref: dict) -> dict:
    """Every number the training comparison can take (see the module doc),
    and where the worst leaf is.  ``limits/<workload>.json`` names the ones
    a cell compares."""
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                 ref["losses"])]
    med = float(np.median(list(ref["grad"].values())))
    keep = {k for k, v in ref["grad"].items() if v >= GRAD_FLOOR * med}
    grad = leaf_gaps(prog["grad"], ref["grad"])
    change = leaf_gaps(prog["change"], ref["change"], keep)
    worst = lambda g: max(g, key=g.get)
    return {"loss_gap": max(loss), "loss_gap_first": loss[0],
            "grad_gap": max(grad.values()),
            "grad_gap_median": float(np.median(list(grad.values()))),
            "change_gap": max(change.values()),
            "change_gap_median": float(np.median(list(change.values()))),
            "grad_leaf": worst(grad), "change_leaf": worst(change),
            "left_out": sorted(set(ref["grad"]) - keep)}

