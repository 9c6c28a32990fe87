"""The bulk token generator and the batch sampler."""
import numpy as np

import tokens


def test_bulk_equals_loop():
    for seed in (0, 7, 2**31 + 3):
        a = tokens.bigram_stream(3, 500, 97, seed)
        b = tokens.bigram_stream_loop(3, 500, 97, seed)
        assert a.dtype == np.int32 and a.shape == (3, 500)
        np.testing.assert_array_equal(a, b)


def test_bigram_structure():
    s = tokens.bigram_stream(2, 20000, 50, 1, successors=3, jump=0.0)
    # with no jumps every step is one of its token's 3 successors
    pairs = {(int(x), int(y)) for row in s for x, y in zip(row, row[1:])}
    assert max(sum(1 for p in pairs if p[0] == t) for t in range(50)) <= 3


def test_sample_matches_the_trainer_feed():
    from repro.data.pipeline import LMBatcher
    s = tokens.bigram_stream(4, 1000, 97, 5)
    ours = tokens.sample(s, np.random.default_rng(9), 32, 2)
    theirs = LMBatcher(s, 32, 2).sample(np.random.default_rng(9))
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]))
    np.testing.assert_array_equal(ours["tokens"][..., 1:],
                                  ours["labels"][..., :-1])
