"""Loop-aware analytic cost walker (repro.roofline.jaxpr_cost)."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.jaxpr_cost import analytic_cost, jaxpr_cost


def _w(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


class TestWalker:
    def test_matmul_flops_exact(self):
        c = analytic_cost(lambda a, b: a @ b, _w(64, 128), _w(128, 32))
        assert c["flops"] == 2 * 64 * 128 * 32

    def test_batched_dot(self):
        c = analytic_cost(
            lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
            _w(4, 8, 16), _w(4, 16, 32))
        assert c["flops"] == 2 * 4 * 8 * 16 * 32

    def test_scan_multiplies(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                                length=7)[0]
        one = analytic_cost(lambda x: x @ x, _w(64, 64))["flops"]
        assert analytic_cost(f, _w(64, 64))["flops"] >= 7 * one

    def test_nested_scans(self):
        def f(x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ ci, None
                return jax.lax.scan(inner, c, None, length=3)[0], None
            return jax.lax.scan(outer, x, None, length=5)[0]
        one = analytic_cost(lambda x: x @ x, _w(32, 32))["flops"]
        c = analytic_cost(f, _w(32, 32))["flops"]
        assert c >= 15 * one

    def test_cond_takes_max(self):
        def f(x):
            return jax.lax.cond(x.sum() > 0,
                                lambda v: v @ v @ v,  # 2 matmuls
                                lambda v: v @ v,      # 1 matmul
                                x)
        one = analytic_cost(lambda x: x @ x, _w(32, 32))["flops"]
        c = analytic_cost(f, _w(32, 32))["flops"]
        assert 2 * one <= c < 3.5 * one

    def test_jit_transparent(self):
        c1 = analytic_cost(lambda a: a @ a, _w(64, 64))
        c2 = analytic_cost(jax.jit(lambda a: a @ a), _w(64, 64))
        assert c1["flops"] == c2["flops"]

    def test_grad_counts_backward(self):
        fwd = analytic_cost(lambda a, b: jnp.sum(a @ b),
                            _w(64, 64), _w(64, 64))["flops"]
        bwd = analytic_cost(
            jax.grad(lambda a, b: jnp.sum(a @ b), argnums=(0, 1)),
            _w(64, 64), _w(64, 64))["flops"]
        assert bwd >= 2 * fwd * 0.9  # two transpose matmuls

    def test_shard_map_counts_all_shards(self, rules):
        from jax.sharding import PartitionSpec as P
        body = jax.shard_map(lambda x: x @ x, mesh=rules.mesh,
                             in_specs=P(None, None),
                             out_specs=P(None, None), check_vma=False)
        c = analytic_cost(body, _w(32, 32))["flops"]
        # 1-device mesh -> exactly one shard's flops
        assert c >= 2 * 32 * 32 * 32

    def test_unknown_shard_map_body_key_warns(self):
        """A shard_map equation whose body-jaxpr param key is unknown to
        compat._SHARD_MAP_BODY_KEYS (a future JAX rename) must not be
        silently priced at zero: warn by default, raise under strict."""
        inner = SimpleNamespace(eqns=[])
        eqn = SimpleNamespace(
            primitive=SimpleNamespace(name="shard_map"),
            params={"renamed_body_jaxpr": SimpleNamespace(jaxpr=inner),
                    "mesh": None},
            invars=[], outvars=[])
        fake = SimpleNamespace(eqns=[eqn])
        with pytest.warns(RuntimeWarning, match="no recognizable body"):
            f, b = jaxpr_cost(fake)
        assert (f, b) == (0.0, 0.0)
        with pytest.raises(ValueError, match="_SHARD_MAP_BODY_KEYS"):
            jaxpr_cost(fake, strict=True)

    def test_known_shard_map_key_does_not_warn(self, rules):
        """The real shard_map lowering must keep resolving silently."""
        import warnings

        from jax.sharding import PartitionSpec as P
        body = jax.shard_map(lambda x: x @ x, mesh=rules.mesh,
                             in_specs=P(None, None),
                             out_specs=P(None, None), check_vma=False)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            analytic_cost(body, _w(32, 32), strict=True)

    def test_train_step_close_to_6nd(self, rules):
        from repro.configs import get_tiny
        from repro.models.model import Model
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.train.step import make_train_step
        cfg = get_tiny("qwen2.5-3b")
        m = Model(cfg, rules)
        params = m.init(jax.random.key(0))
        step = make_train_step(m, AdamWConfig())
        b, s = 4, 128
        batch = {"tokens": jnp.zeros((b, s), jnp.int32),
                 "labels": jnp.zeros((b, s), jnp.int32)}
        c = analytic_cost(step, params, adamw_init(params), batch)
        nd6 = 6 * m.count_params() * b * s
        # remat + attention + optimizer put it above 6ND but within ~2x
        assert nd6 * 0.9 < c["flops"] < nd6 * 2.5
