"""The traffic generator: seeds decide the tokens, never the work."""
import numpy as np

from bench import generate as G

TRAIN = {"seq": 64, "rows": 32, "corpus": {"order_mix": 0.8}}
BIG = 2 ** 40 + 7  # seeds run past 32 bits


def test_train_rows_are_seeded():
    a = G.train_rows(TRAIN, 500, BIG)
    assert a.shape == (32, 64) and a.dtype == np.int32
    assert np.array_equal(a, G.train_rows(TRAIN, 500, BIG))
    assert not np.array_equal(a, G.train_rows(TRAIN, 500, BIG + 1))
    assert a.min() >= 0 and a.max() < 500


def test_seed32_keeps_the_high_bits():
    assert G.seed32(BIG) != G.seed32(7)
    assert 0 <= G.seed32(BIG) < 2 ** 31
