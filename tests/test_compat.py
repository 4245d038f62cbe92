"""repro.compat: the JAX API shims run on the installed JAX."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat


class TestInstalledVersion:
    """The shims, and the shard_map pattern the model uses, run on the
    installed JAX."""

    def test_shard_map_executes(self, rules):
        fn = jax.shard_map(lambda x: x * 2, mesh=rules.mesh,
                           in_specs=P(None, None),
                           out_specs=P(None, None), check_vma=False)
        y = fn(jnp.ones((4, 4)))
        np.testing.assert_allclose(np.asarray(y), 2 * np.ones((4, 4)))

    def test_axis_size_inside_body(self, rules):
        def body(x):
            return x + jax.lax.axis_size("model")

        fn = jax.shard_map(body, mesh=rules.mesh, in_specs=P(None, None),
                           out_specs=P(None, None), check_vma=False)
        y = fn(jnp.zeros((2, 2)))
        # single-device mesh: model axis has size 1
        np.testing.assert_allclose(np.asarray(y), np.ones((2, 2)))

    def test_make_mesh_axis_names(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        assert mesh.axis_names == ("data", "model")
        assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1

    def test_shard_map_jaxpr_helpers(self, rules):
        fn = jax.shard_map(lambda x: x @ x, mesh=rules.mesh,
                           in_specs=P(None, None),
                           out_specs=P(None, None), check_vma=False)
        closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4, 4), jnp.float32))
        eqn = next(e for e in closed.jaxpr.eqns
                   if e.primitive.name == "shard_map")
        body = compat.shard_map_body(eqn.params)
        assert body is not None and len(body.eqns) >= 1
        assert compat.shard_map_mesh_size(eqn.params) == 1
