"""Whether a stream cell's decisions are the reference's.

Every call the limiter made in the run (prefill, warm-up and the
window) is replayed through the frozen reference, key by key, in call
order; each call carries one timestamp and one permit per request, so
``reference/groups.py`` decides it in closed form: how many of each
key's requests in the call are allowed (the first ones).  Two numbers
are compared, each with the limit 0:

- ``key_count_errors``: over every key of every call, keys whose number
  of allowed requests in the call differs from the reference's;
- ``mismatches``: decisions that differ from the reference, over every
  request of a sample of keys drawn from the seed (plus key 0, the
  hottest key of a Zipf mix, whose thousands of requests per call are
  the longest decision sequences), at every position of every call:
  this holds the order within a key, which the counts cannot see.
"""

from __future__ import annotations

import os
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.gen import rng_for
from reference.config import RateLimitConfig
from reference.groups import groups_for

SAMPLE_KEYS = 32_767
THREADS = min(8, os.cpu_count() or 1)
LOOKAHEAD = 4


class StreamCheck:
    def __init__(self, config: dict, seed: int, dtype=np.int64):
        n = config["keys"]["count"]
        self.groups = groups_for(config["algorithm"],
                                 RateLimitConfig(**config["policy"]), n,
                                 dtype)
        sample = rng_for(seed, 9).choice(n, size=min(SAMPLE_KEYS, n),
                                         replace=False)
        self.in_sample = np.zeros(n, bool)
        self.in_sample[sample] = True
        self.in_sample[0] = True
        self._pool = ThreadPoolExecutor(THREADS)
        self.compared = 0
        self.mismatches = 0
        self.key_count_errors = 0
        self.keys_compared = 0
        self.uniques = []           # distinct keys of each call, in order

    def _prepare(self, ids: np.ndarray, got):
        """A call's keys, their request counts and the program's allowed
        counts (from one sort of ``2 * id + answer``), and for the
        sampled positions (sorted by key, then call order) each one's
        rank among its key's requests and its key's index in ``keys``.
        An answer of the wrong shape counts as all denied, and every key
        of the call as wrong."""
        got = np.asarray(got, dtype=bool)
        lost = got.shape != ids.shape
        if lost:
            got = np.zeros(ids.shape, bool)
        v = np.sort(ids * 2 + got)
        k = v >> 1
        starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
        keys = k[starts]
        counts = np.diff(np.r_[starts, len(v)])
        got_per_key = np.add.reduceat(v & 1, starts)
        pos = np.flatnonzero(self.in_sample[ids])
        kix = np.searchsorted(keys, ids[pos])
        order = np.argsort(kix, kind="stable")
        pos, kix = pos[order], kix[order]
        first = np.searchsorted(kix, kix, side="left")
        rank = np.arange(len(kix)) - first
        return lost, got, keys, counts, got_per_key, pos, kix, rank

    def _apply(self, keys, counts, now_ms: int) -> np.ndarray:
        """The reference over a call's keys, in disjoint slices of keys
        on several threads (numpy releases the GIL on large arrays)."""
        if len(keys) < 1 << 16:
            return self.groups.apply(keys, counts, now_ms)
        cut = np.linspace(0, len(keys), THREADS + 1).astype(int)
        return np.concatenate(list(self._pool.map(
            lambda i: self.groups.apply(keys[cut[i]:cut[i + 1]],
                                        counts[cut[i]:cut[i + 1]], now_ms),
            range(THREADS))))

    def close(self) -> None:
        self._pool.shutdown()

    def replay(self, calls) -> None:
        """Check ``(ids, now_ms, answers)`` calls in order; each call's
        preparation runs ahead on a second pool while the reference
        decides the calls before it."""
        with ThreadPoolExecutor(LOOKAHEAD) as prep:
            pending = deque()
            for ids, now_ms, got in calls:
                pending.append((prep.submit(self._prepare, ids, got),
                                now_ms))
                if len(pending) > LOOKAHEAD:
                    self._decide(*pending.popleft())
            while pending:
                self._decide(*pending.popleft())

    def _decide(self, prepared, now_ms: int) -> None:
        lost, got, keys, counts, got_per_key, pos, kix, rank = \
            prepared.result()
        allowed = self._apply(keys, counts, now_ms)
        self.uniques.append(len(keys))
        self.keys_compared += len(keys)
        self.key_count_errors += (len(keys) if lost else int(
            np.count_nonzero(got_per_key != allowed)))
        want = rank < allowed[kix]
        self.compared += len(pos)
        self.mismatches += int(np.count_nonzero(want != got[pos]))

    def checks(self) -> dict:
        return {"mismatches": {"value": self.mismatches, "limit": 0},
                "key_count_errors": {"value": self.key_count_errors,
                                     "limit": 0}}

    def info(self) -> dict:
        return {"decisions_compared": self.compared,
                "key_counts_compared": self.keys_compared,
                "calls_replayed": len(self.uniques)}

    @property
    def correct(self) -> bool:
        return (self.mismatches == 0 and self.key_count_errors == 0
                and self.compared > 0)
