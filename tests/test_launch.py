"""The launch drivers end to end on the CPU at the tiny size, and the mesh
they build.

``main()`` of each driver places JAX's compile cache only where the caller
has not: these tests set ``JAX_COMPILATION_CACHE_DIR`` so the drivers leave
this process's cache configuration alone.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.launch import serve, train
from repro.sharding.rules import make_rules

TINY = ["--arch", "mamba2-370m", "--tiny", "--nodes", "1"]


@pytest.fixture
def placed_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    before = jax.config.jax_compilation_cache_dir
    yield
    assert jax.config.jax_compilation_cache_dir == before


def test_train_cold_then_warm_resumes(tmp_path, placed_cache):
    args = TINY + ["--batch", "2", "--seq-len", "16", "--ckpt-every", "2",
                   "--workdir", str(tmp_path / "job")]
    cold = train.main(args + ["--steps", "2"])
    assert cold["resume_step"] is None
    assert [s["step"] for s in cold["saved"]] == [2]
    assert cold["device"]["platform"] == jax.devices()[0].platform
    assert set(cold["startup_s"]) == {"image_load", "env_setup",
                                      "model_init", "total"}

    warm = train.main(args + ["--steps", "1"])
    assert warm["resume_step"] == 2
    assert warm["losses"][0][0] == 2
    assert warm["saved"] == []
    assert all(math.isfinite(loss)
               for _, loss in cold["losses"] + warm["losses"])
    # one device holds every parameter byte
    (only,) = warm["param_bytes_per_device"].values()
    assert only == sum(cold["param_bytes_per_device"].values())


def test_serve_answers_every_request(tmp_path, placed_cache):
    out = serve.main(TINY + ["--requests", "5", "--new-tokens", "3",
                             "--batch", "2", "--cache-len", "32",
                             "--workdir", str(tmp_path / "serve")])
    reqs = out["requests"]
    assert len(reqs) == 5
    assert [len(r["generated"]) for r in reqs] == [3] * 5
    assert out["device"]["platform"] == jax.devices()[0].platform


@pytest.mark.parametrize("text, mesh", [("1x1", (1, 1)), ("2X4", (2, 4))])
def test_parse_mesh(text, mesh):
    assert train.parse_mesh(text) == mesh


def test_parse_mesh_rejects_garbage():
    import argparse
    with pytest.raises(argparse.ArgumentTypeError):
        train.parse_mesh("4")


def test_make_mesh_axes_are_auto():
    """Explicit axes (the default of ``jax.make_mesh`` since 0.7) make
    ``with_sharding_constraint`` refuse the rules' specs."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_constrain_under_jit():
    rules = make_rules(compat.make_mesh((1, 1), ("data", "model")))
    x = jnp.arange(8.0).reshape(2, 4)
    y = jax.jit(lambda v: rules.constrain(v * 2, P("data", "model")))(x)
    np.testing.assert_allclose(np.asarray(y), 2 * np.asarray(x))
