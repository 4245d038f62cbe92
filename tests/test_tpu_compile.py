"""Compile the main path at published widths for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached.  That refuses what interpret mode
accepts (block shapes off the (8, 128) tiling, too much VMEM) and programs
that do not fit the chip's 16 GB of HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each pytest-xdist
worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd import ssd_chunked_kernel
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serve.step import jit_decode_step, jit_prefill
from repro.sharding.rules import make_rules
from repro.train.step import jit_train_step

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mamba2(topo):
    """mamba2-370m at its registry widths on a 1x1 mesh of one v5e."""
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            devices=topo.devices[:1])
    return Model(get_config("mamba2-370m"), make_rules(mesh))


def _hbm_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _param_shapes(model):
    return jax.eval_shape(model.init, jax.random.key(0))


@pytest.mark.parametrize("window", [0, 4096])
def test_flash_attention_compiles_at_qwen_widths(one_chip, window):
    """qwen2.5-3b: Hq 16, Hkv 2, Dh 128, S 4096, bf16 (and mixtral's
    4096-token window)."""
    q = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)
    fn = functools.partial(flash_attention, causal=True, window=window)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_compiles_at_mamba2_widths(one_chip):
    """mamba2-370m: H 32, P 64, N 128, one B/C group, chunk 256, S 4096."""
    b, s, h, p, g, n = 1, 4096, 32, 64, 1, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((b, s, h, p), jnp.bfloat16), sds((b, s, h), jnp.float32),
            sds((h,), jnp.float32), sds((b, s, g, n), jnp.bfloat16),
            sds((b, s, g, n), jnp.bfloat16), sds((h,), jnp.float32))
    fn = functools.partial(ssd_chunked_kernel, chunk=256)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba2_train_step_fits_one_chip(mamba2):
    """Batch 4 x 1024 with f32 params and AdamW state, donated."""
    params = _param_shapes(mamba2)
    opt = jax.eval_shape(adamw_init, params)
    tokens = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    step = jit_train_step(mamba2, AdamWConfig(), 4)
    compiled = step.lower(params, opt,
                          {"tokens": tokens, "labels": tokens}).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


def test_mamba2_prefill_and_decode_fit_one_chip(mamba2):
    """Batch 4, a 512-token prompt, a 2048-token cache."""
    batch, cache_len = 4, 2048
    params = _param_shapes(mamba2)
    prefill = jit_prefill(mamba2, batch, cache_len).lower(
        params, {"tokens": jax.ShapeDtypeStruct((batch, 512), jnp.int32)}
    ).compile()
    assert _hbm_bytes(prefill) < V5E_HBM_BYTES

    cache = jax.eval_shape(lambda: mamba2.init_cache(batch, cache_len))
    decode = jit_decode_step(mamba2, batch, cache_len).lower(
        params, jax.ShapeDtypeStruct((batch, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    assert _hbm_bytes(decode) < V5E_HBM_BYTES
