"""The traffic generator replays a seed exactly, and the seed changes the
tokens but not the work."""
import numpy as np

from bench import traffic


def test_decode_prompts_replay_and_differ_by_seed():
    mix = traffic.load_mix("decode-b8")
    a = traffic.decode_prompts(mix, 2**33 + 1, 50304)
    b = traffic.decode_prompts(mix, 2**33 + 1, 50304)
    c = traffic.decode_prompts(mix, 2**33 + 2, 50304)
    assert a.shape == (8, 512) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert (a != c).mean() > 0.9
    assert a.min() >= 0 and a.max() < 50304
