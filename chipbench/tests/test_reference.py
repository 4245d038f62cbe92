"""The plain references against the program at the registry's tiny sizes
on the CPU, from one set of seeded weights.  The program runs its
activations in bfloat16, so the bounds are bfloat16's."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference.precision import F32, FP8
from chipbench.tests.conftest import tiny_config


def program(name: str, m: dict):
    from repro.models.model import Model
    from repro.sharding.rules import single_device_rules
    return Model(harness.program_config({"arch": name, "model": m}),
                 single_device_rules())


def tokens(shape, vocab, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, shape).astype(np.int32))


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_reference_imports_nothing_of_the_program():
    for f in (harness.HERE / "reference").glob("*.py"):
        assert "repro" not in f.read_text(), f


def test_mamba2_loss_and_gradient_agree_with_the_program():
    m = tiny_config("mamba2-370m")["model"]
    ref = harness.load_module(harness.HERE / "reference" / "mamba2.py")
    model = program("mamba2-370m", m)
    params = ref.init(m, jax.random.key(3))
    assert jax.tree.structure(params) == jax.tree.structure(
        model.init(jax.random.key(0)))
    tok, lab = tokens((2, 96), m["vocab_size"]), tokens((2, 96),
                                                        m["vocab_size"], 1)
    lp, gp = jax.value_and_grad(
        lambda p: model.train_loss(p, {"tokens": tok, "labels": lab})[0])(
            params)
    lr, gr = jax.value_and_grad(lambda p: ref.loss(m, p, tok, lab))(params)
    assert abs(float(lp) - float(lr)) < 2e-3 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert rel(a, b) < 5e-2


def test_ssd_listing_agrees_with_a_step_by_step_recurrence():
    ref = harness.load_module(harness.HERE / "reference" / "mamba2.py")
    r = np.random.default_rng(0)
    b, s, h, p, g, n = 2, 40, 4, 8, 1, 6
    x = jnp.asarray(r.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = -jnp.asarray(r.uniform(1, 4, (h,)), jnp.float32)
    B = jnp.asarray(r.normal(size=(b, s, g, n)), jnp.float32)
    C = jnp.asarray(r.normal(size=(b, s, g, n)), jnp.float32)
    y, final = ref.ssd(x, dt, A, B, C, chunk=16)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        state = state * jnp.exp(dt[:, t] * A)[..., None, None] + jnp.einsum(
            "bn,bhp->bhpn", B[:, t, 0], x[:, t] * dt[:, t, :, None])
        ys.append(jnp.einsum("bn,bhpn->bhp", C[:, t, 0], state))
    assert rel(y, jnp.stack(ys, 1)) < 1e-5
    assert rel(final, state) < 1e-5


def test_fp8_control_departs_further_than_the_program():
    m = tiny_config("mamba2-370m")["model"]
    ref = harness.load_module(harness.HERE / "reference" / "mamba2.py")
    params = ref.init(m, jax.random.key(5))
    tok = tokens((2, 48), m["vocab_size"])
    f = lambda dots: ref.logits(m, params, ref.hidden(m, params, tok, dots),
                                dots)
    ctl = rel(f(FP8), f(F32))
    model = program("mamba2-370m", m)
    h = model.apply_layers(model._maybe_cast_params(params),
                           model._embed_inputs(params, {"tokens": tok}),
                           mode="train", positions=jnp.arange(48))[0]
    prog = rel(model._logits(params, h), f(F32))
    assert ctl > 3 * prog


def test_fp8_control_rounds_the_backward_products_too():
    """The control's gradients come from float8 products in the backward
    pass as well (operands e4m3, incoming gradients e5m2)."""
    r = np.random.default_rng(1)
    a = jnp.asarray(r.normal(size=(8, 16)), jnp.float32)
    b = jnp.asarray(r.normal(size=(16, 4)), jnp.float32)
    w = jnp.asarray(r.normal(size=(8, 4)), jnp.float32)
    grad = lambda dots: jax.grad(
        lambda x: jnp.sum(dots.einsum("ik,kj->ij", x, b) * w))(a)
    straight = jax.grad(lambda x: jnp.sum(
        F32.einsum("ik,kj->ij", x, FP8._op(b)) * w))(a)
    assert rel(grad(FP8), grad(F32)) > 1e-3
    assert rel(grad(FP8), straight) > 1e-3
