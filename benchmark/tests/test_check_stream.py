"""The stream check outside its sample: a fault that keeps a call's
allowed total but moves allows between keys that are not sampled still
turns ``correct`` false, through the per-key counts."""

import numpy as np
import pytest

from control import closed_form_decisions
from harness import check_stream, gen
from harness.check_stream import StreamCheck
from reference.config import RateLimitConfig
from reference.groups import groups_for

CONFIG = {"algorithm": "token_bucket", "keys": {"count": 4000},
          "policy": {"max_permits": 3, "window_ms": 60000,
                     "refill_rate": 1.0}}


def _calls(seed, n_calls=3, ids=6000):
    keys = {"count": 4000, "distribution": "zipf", "zipf_a": 1.1}
    return gen.stream_calls(seed, keys, ids, n_calls)


def _decide(calls):
    groups = groups_for("token_bucket", RateLimitConfig(**CONFIG["policy"]),
                        4000, np.int64)
    return [closed_form_decisions(groups, ids, 1000 * i)
            for i, ids in enumerate(calls)]


@pytest.mark.parametrize("seed", [5, 2_147_483_700])
def test_swap_outside_the_sample_is_caught(monkeypatch, seed):
    monkeypatch.setattr(check_stream, "SAMPLE_KEYS", 10)
    calls = _calls(seed)
    answers = _decide(calls)
    check = StreamCheck(CONFIG, seed)
    ids, got = calls[-1], answers[-1].copy()
    out = ~check.in_sample[ids]
    a = np.flatnonzero(out & got)[0]
    b = np.flatnonzero(out & ~got & (ids != ids[a]))[0]
    got[a], got[b] = False, True
    check.replay((ids_i, 1000 * i, got_i) for i, (ids_i, got_i)
                 in enumerate(zip(calls, answers[:-1] + [got])))
    check.close()
    assert check.mismatches == 0
    assert check.key_count_errors == 2
    assert not check.correct


def test_exact_answers_check_out():
    calls = _calls(9)
    check = StreamCheck(CONFIG, 9)
    check.replay((ids, 1000 * i, got)
                 for i, (ids, got) in enumerate(zip(calls, _decide(calls))))
    check.close()
    assert check.correct and check.keys_compared == sum(
        len(np.unique(c)) for c in calls)
