"""The frozen oracle's semantics for a whole call at once.

Every request of one stream call carries the same ``now`` and one
permit.  For one key with ``c`` such requests the oracle's sequence
collapses to a closed form:

- token bucket: refill once, then the first ``min(c, tokens // ONE)``
  requests are allowed; each later request sees no elapsed time, and a
  denial writes nothing;
- sliding window (permits 1): request ``i`` sees the estimate ``E + i``
  after ``i`` allows, so the first ``clip(max - E, 0, c)`` are allowed,
  and each allowed request increments the current bucket by one and
  sets its deadline to ``now + window`` (quirk Q2 cannot fire at one
  permit).

``apply`` takes distinct keys of a call with their request counts and
returns how many of each key's requests are allowed (in call order, the
first ones).  State is one row per key id (``FIELDS`` columns).  Calls
on disjoint keys may run in parallel threads.  ``dtype`` is the
precision of state and arithmetic: int64 is the reference; int32 is the
control (the nearest precision below the one the configurations state).
"""

from __future__ import annotations

import numpy as np

from .config import TOKEN_FP_ONE, RateLimitConfig


def _const(x: int, dtype) -> np.ndarray:
    # Through int64 so that int32 wraps like a 32-bit lane would.
    return np.array(int(x), dtype=np.int64).astype(dtype)


class _Rows:
    FIELDS: tuple = ()

    def __init__(self, num_keys: int, dtype):
        self.dtype = dtype
        self.state = np.zeros((num_keys, len(self.FIELDS)), dtype)

    def _load(self, keys):
        rows = np.take(self.state, keys, axis=0)
        return [rows[:, i] for i in range(rows.shape[1])]

    def _store(self, keys, *cols) -> None:
        self.state[keys] = np.stack(cols, axis=1)


class TokenBucketGroups(_Rows):
    FIELDS = ("present", "tokens", "last", "deadline")

    def __init__(self, config: RateLimitConfig, num_keys: int,
                 dtype=np.int64):
        config.validate()
        super().__init__(num_keys, dtype)
        c = lambda x: _const(x, dtype)  # noqa: E731
        self.cap = c(config.max_permits_fp)
        self.rate = c(config.refill_rate_fp)
        self.one = c(TOKEN_FP_ONE)
        self.ttl = c(2 * config.window_ms)
        self.clamp = c(config.max_permits_fp
                       // max(config.refill_rate_fp, 1) + 1)

    def apply(self, keys: np.ndarray, counts: np.ndarray,
              now_ms: int) -> np.ndarray:
        now = _const(now_ms, self.dtype)
        present, tok, last, deadline = self._load(keys)
        fresh = (present == 0) | (now >= deadline)
        tok = np.where(fresh, self.cap, tok)
        last = np.where(fresh, now, last)
        elapsed = np.minimum(now - last, self.clamp)
        refilled = np.minimum(self.cap, tok + elapsed * self.rate)
        allowed = np.clip(refilled // self.one, 0, None).astype(np.int64)
        allowed = np.minimum(allowed, counts)
        w = allowed > 0
        one = np.ones(int(w.sum()), self.dtype)
        self._store(keys[w], one,
                    refilled[w] - allowed[w].astype(self.dtype) * self.one,
                    one * now, one * (now + self.ttl))
        return allowed


class SlidingWindowGroups(_Rows):
    """Per key: bucket A (window ``ws``) and bucket B (window ``ws - w``),
    each a count and an expiry deadline, as the oracle's dict of
    ``(key, window_start)`` buckets holds them."""

    FIELDS = ("present", "ws", "ca", "da", "cb", "db")

    def __init__(self, config: RateLimitConfig, num_keys: int,
                 dtype=np.int64):
        config.validate()
        super().__init__(num_keys, dtype)
        self.w = _const(config.window_ms, dtype)
        self.max = _const(config.max_permits, dtype)

    def apply(self, keys: np.ndarray, counts: np.ndarray,
              now_ms: int) -> np.ndarray:
        now = _const(now_ms, self.dtype)
        w = self.w
        curr_ws = (now // w) * w
        rem = now - curr_ws
        present, ws, ca, da, cb, db = self._load(keys)
        is_cur = (present != 0) & (ws == curr_ws)
        is_prev = (present != 0) & (ws == curr_ws - w)
        zero = np.zeros_like(ca)
        curr = np.where(is_cur & (now < da), ca, zero)
        prev = np.where(is_cur, np.where(now < db, cb, zero),
                        np.where(is_prev & (now < da), ca, zero))
        estimate = curr + (prev * (w - rem)) // w
        allowed = np.clip((self.max - estimate).astype(np.int64), 0, None)
        allowed = np.minimum(allowed, counts)
        m = allowed > 0
        # A rolled key's old bucket A becomes bucket B when it was the
        # previous window, and is forgotten when it was older.
        roll, keep = ~is_cur[m], is_prev[m]
        one = np.ones(int(m.sum()), self.dtype)
        self._store(keys[m], one, one * curr_ws,
                    curr[m] + allowed[m].astype(self.dtype), one * (now + w),
                    np.where(roll, np.where(keep, ca[m], 0), cb[m]),
                    np.where(roll, np.where(keep, da[m], 0), db[m]))
        return allowed


def groups_for(algorithm: str, config: RateLimitConfig, num_keys: int,
               dtype=np.int64):
    cls = {"token_bucket": TokenBucketGroups,
           "sliding_window": SlidingWindowGroups}[algorithm]
    return cls(config, num_keys, dtype)
