
import numpy as np
import pytest

from chipbench.streams import TokenStream, jax_seed

SEEDS = [0, 7, 2**31 + 5, 3 * 2**32 + 1]


@pytest.mark.parametrize("seed", SEEDS)
def test_token_stream_is_fixed_by_seed(seed):
    a, b = TokenStream(512, seed), TokenStream(512, seed)
    assert np.array_equal(a.batch(3, 2, 16), b.batch(3, 2, 16))
    assert not np.array_equal(a.batch(3, 2, 16), a.batch(4, 2, 16))
    assert not np.array_equal(a.batch(3, 2, 16),
                              TokenStream(512, seed + 1).batch(3, 2, 16))


def test_token_stream_is_the_training_loops_stream():
    from repro.data.synthetic import SyntheticStream
    for seed in (0, 2**31 + 5):
        assert np.array_equal(TokenStream(50280, seed).batch(100, 2, 64),
                              SyntheticStream(50280, seed).batch(100, 2, 64))


@pytest.mark.parametrize("seed", SEEDS)
def test_jax_seed_fits_31_bits(seed):
    k = jax_seed(seed)
    assert 0 <= k < 2**31 and k == jax_seed(seed)
