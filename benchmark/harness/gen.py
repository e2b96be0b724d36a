"""Traffic generation from a seed (numpy only).

``uniform_stream`` and ``zipf_stream`` are copied from
``ratelimiter_tpu/bench/harness.py`` (PR 21) so that a later change to
the program cannot change the benchmark's traffic; ``stream_calls``
draws a Zipf call as ``zipf_stream`` does, bit for bit, with the
distribution computed once (``tests/test_traffic.py``).  Key ids are
ranks: id 0 is the most frequent key of a Zipf mix.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def uniform_stream(rng, num_keys: int, n: int) -> np.ndarray:
    return rng.integers(0, num_keys, size=n)


def zipf_stream(rng, num_keys: int, n: int, a: float = 1.1) -> np.ndarray:
    # Bounded Zipf via inverse-CDF over ranks (np.random.zipf is unbounded).
    ranks = np.arange(1, num_keys + 1, dtype=np.float64)
    probs = ranks ** (-a)
    probs /= probs.sum()
    return rng.choice(num_keys, size=n, p=probs)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """One independent generator per (seed, purpose, index): the same seed
    gives the same inputs whatever else the run draws."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), *stream])


def _zipf_cdf(num_keys: int, a: float) -> np.ndarray:
    """The cumulative distribution ``rng.choice(..., p=probs)`` searches
    in ``zipf_stream``, computed once for many calls."""
    ranks = np.arange(1, num_keys + 1, dtype=np.float64)
    probs = ranks ** (-a)
    probs /= probs.sum()
    cdf = probs.cumsum()
    cdf /= cdf[-1]
    return cdf


def stream_calls(seed: int, keys: dict, ids_per_call: int,
                 n_calls: int) -> list:
    """The key ids of ``n_calls`` distinct calls, call ``i`` drawn from
    its own generator ``(seed, 1, i)`` on a pool of threads.  A Zipf call
    is ``zipf_stream``'s draw, bit for bit (``rng.choice`` with ``p`` is
    a search of one uniform draw per id in this distribution)."""
    dist = keys["distribution"]
    if dist not in ("zipf", "uniform"):
        raise ValueError(f"unknown key distribution {dist!r}")
    cdf = _zipf_cdf(keys["count"], keys["zipf_a"]) if dist == "zipf" \
        else None

    def one(i):
        rng = rng_for(seed, 1, i)
        if cdf is None:
            return uniform_stream(rng, keys["count"], ids_per_call)
        return cdf.searchsorted(rng.random(ids_per_call), side="right")

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        return list(pool.map(one, range(n_calls)))


def prefill_ids(seed: int, keys: dict, prefill: dict) -> np.ndarray:
    """One call that touches every key once (so the slot index holds the
    whole key space before the window) plus ``share`` of the keys, drawn
    from the seed, a further ``0..max_count`` times each."""
    rng = rng_for(seed, 2)
    n = keys["count"]
    heavy = rng.choice(n, size=int(n * prefill["share"]), replace=False)
    extra = rng.integers(0, prefill["max_count"] + 1, size=len(heavy))
    ids = np.concatenate([np.arange(n, dtype=np.int64),
                          np.repeat(heavy.astype(np.int64), extra)])
    rng.shuffle(ids)
    return ids
