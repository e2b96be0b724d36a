"""The frozen reference: still the program's oracle, and the per-call
closed form (``groups.py``) still the frozen oracle."""

import numpy as np
import pytest

from reference import oracle as frozen
from reference.config import RateLimitConfig
from reference.groups import groups_for

T0 = 1_760_000_020_000
POLICIES = [
    ("token_bucket", dict(max_permits=50, window_ms=60_000, refill_rate=10.0)),
    ("token_bucket", dict(max_permits=3, window_ms=1_000, refill_rate=2.5)),
    ("sliding_window", dict(max_permits=100, window_ms=60_000)),
    ("sliding_window", dict(max_permits=4, window_ms=1_000)),
]


def _oracle(module, algo, policy):
    cls = {"token_bucket": module.TokenBucketOracle,
           "sliding_window": module.SlidingWindowOracle}[algo]
    return cls(module.RateLimitConfig(**policy))


def _calls(seed, n_keys, window_ms):
    """Seeded calls: a few ids each (with repeats), timestamps that stay,
    step inside a window, cross windows and pass TTLs."""
    rng = np.random.default_rng(seed)
    now = T0
    for _ in range(60):
        now += int(rng.choice([0, 1, 37, 100, window_ms // 3, window_ms,
                               3 * window_ms]))
        ids = rng.zipf(1.3, size=int(rng.integers(1, 40))) % n_keys
        yield now, ids.astype(np.int64)


def _oracle_decisions(oracle, calls):
    out = []
    for now, ids in calls:
        out.append(np.array([oracle.try_acquire(str(k), 1, now).allowed
                             for k in ids.tolist()]))
    return out


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("algo,policy", POLICIES)
def test_frozen_oracle_equals_program_oracle(seed, algo, policy):
    from ratelimiter_tpu.semantics import oracle as program

    calls = list(_calls(seed, 25, policy["window_ms"]))
    want = _oracle_decisions(_oracle(program, algo, policy), calls)
    got = _oracle_decisions(_oracle(frozen, algo, policy), calls)
    for g, w in zip(got, want):
        assert (g == w).all()


def call_decisions(groups, ids, now):
    """Per-position decisions of one call from the closed form: the
    first n_allowed occurrences of each key are allowed."""
    keys, inverse, counts = np.unique(ids, return_inverse=True,
                                      return_counts=True)
    allowed = groups.apply(keys, counts, now)
    order = np.argsort(inverse, kind="stable")
    rank = np.empty(len(ids), np.int64)
    starts = np.cumsum(counts) - counts
    rank[order] = np.arange(len(ids)) - np.repeat(starts, counts)
    return rank < allowed[inverse]


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("algo,policy", POLICIES)
def test_groups_equal_frozen_oracle(seed, algo, policy):
    calls = list(_calls(seed, 25, policy["window_ms"]))
    want = _oracle_decisions(_oracle(frozen, algo, policy), calls)
    groups = groups_for(algo, RateLimitConfig(**policy), 25)
    for (now, ids), w in zip(calls, want):
        assert (call_decisions(groups, ids, now) == w).all()


@pytest.mark.parametrize("algo,policy", POLICIES[1::2])
def test_int32_control_departs(algo, policy):
    """The control (state and arithmetic in int32) decides differently
    from the int64 reference on the same calls."""
    calls = list(_calls(0, 25, policy["window_ms"]))
    ref = groups_for(algo, RateLimitConfig(**policy), 25)
    ctl = groups_for(algo, RateLimitConfig(**policy), 25, np.int32)
    differ = sum(int((call_decisions(ref, ids, now)
                      != call_decisions(ctl, ids, now)).sum())
                 for now, ids in calls)
    assert differ > 0
