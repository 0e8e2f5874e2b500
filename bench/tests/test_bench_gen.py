"""Inputs from the seed: the same seed and job give the same calibration
tokens, any other gives others, for seeds beyond 32 bits too."""
import numpy as np
import pytest

from bench import gen
from bench import weights as W

BIG = 2 ** 33 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 31 + 5])
def test_calibration_tokens_by_seed_and_job(seed):
    a = gen.calibration_tokens(seed, 0, 2, 3, 4, 100)
    assert a.shape == (2, 3, 4) and a.dtype == np.int32
    assert 0 <= a.min() and a.max() < 100
    np.testing.assert_array_equal(a, gen.calibration_tokens(seed, 0, 2, 3, 4,
                                                            100))
    assert not np.array_equal(a, gen.calibration_tokens(seed, 1, 2, 3, 4,
                                                        100))
    assert not np.array_equal(a, gen.calibration_tokens(seed + 1, 0, 2, 3,
                                                        4, 100))


def test_weight_keys_differ_past_32_bits():
    a, b = W.root_key(5), W.root_key(5 + 2 ** 31)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(W.root_key(BIG)),
                                  np.asarray(W.root_key(BIG)))
