"""The traffic generator: the same seed gives the same calls, each call
is drawn anew, and a Zipf call is the copied ``zipf_stream``'s draw."""

import numpy as np
import pytest

from harness import gen

ZIPF = {"count": 5000, "distribution": "zipf", "zipf_a": 1.1}
UNIFORM = {"count": 5000, "distribution": "uniform"}


@pytest.mark.parametrize("seed", [3, 3_000_000_123])
def test_zipf_call_is_zipf_stream(seed):
    calls = gen.stream_calls(seed, ZIPF, 4096, 3)
    for i, ids in enumerate(calls):
        want = gen.zipf_stream(gen.rng_for(seed, 1, i), 5000, 4096, 1.1)
        assert np.array_equal(ids, want) and ids.dtype == np.int64


@pytest.mark.parametrize("keys", [ZIPF, UNIFORM])
def test_calls_are_seeded_and_distinct(keys):
    a = gen.stream_calls(17, keys, 4096, 4)
    b = gen.stream_calls(17, keys, 4096, 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(a[i], a[j])
                   for i in range(4) for j in range(i))
