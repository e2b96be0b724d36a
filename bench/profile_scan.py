"""Compare cumulative-op formulations on the real device: compile time and
fetched-run time (np.asarray round trip; a remote link adds a fixed floor, so
compare deltas, not absolutes).

Run: python bench/profile_scan.py [B ...]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")


def hier_scan(op, x, identity, chunk=4096):
    """Two-level associative scan: inner scans of length `chunk`, one outer
    scan over the B/chunk row totals.  Equivalent to associative_scan(op, x)
    for associative ops; compiles orders of magnitude faster at mega-batch
    sizes because every scan axis stays small."""
    n = x.shape[0]
    rows = n // chunk
    x2 = x.reshape(rows, chunk)
    inner = jax.lax.associative_scan(op, x2, axis=1)
    tots = inner[:, -1]
    outer = jax.lax.associative_scan(op, tots)
    base = jnp.concatenate([jnp.full((1,), identity, x.dtype), outer[:-1]])
    return op(inner, base[:, None]).reshape(n)


def timed(name, fn, *args):
    t0 = time.perf_counter()
    c = jax.jit(fn).lower(*args).compile()
    tc = time.perf_counter() - t0
    np.asarray(c(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(c(*args))
        times.append(time.perf_counter() - t0)
    print(f"  {name}: compile {tc:6.1f}s  fetch-run {min(times)*1000:7.1f} ms",
          flush=True)
    return c


def main():
    sizes = [int(x) for x in sys.argv[1:]] or [1 << 21]
    rng = np.random.default_rng(0)
    for B in sizes:
        print(f"B={B}", flush=True)
        xi = jnp.asarray(rng.integers(0, 1 << 20, B, dtype=np.int32))
        xl = xi.astype(jnp.int64)

        timed("lax.cummax_i32", jax.lax.cummax, xi)
        timed("lax.cumsum_i64", jax.lax.cumsum, xl)
        timed("hier_cummax_i32",
              lambda v: hier_scan(jnp.maximum, v, np.int32(-2**31)), xi)
        timed("hier_cumsum_i64", lambda v: hier_scan(jnp.add, v, 0), xl)
        # correctness spot check
        a = np.asarray(jax.jit(
            lambda v: hier_scan(jnp.maximum, v, np.int32(-2**31)))(xi))
        b = np.maximum.accumulate(np.asarray(xi))
        c = np.asarray(jax.jit(lambda v: hier_scan(jnp.add, v, 0))(xl))
        d = np.cumsum(np.asarray(xl))
        print(f"  hier correct: cummax={bool((a==b).all())} "
              f"cumsum={bool((c==d).all())}", flush=True)


if __name__ == "__main__":
    main()
