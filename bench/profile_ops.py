"""Ground-truth op timings, each forced to completion by a fetch.

Written for the TPU reached over a remote link before PR 1, where
block_until_ready did not block; so every timing here forces
completion by fetching a scalar reduction (8 bytes D2H) and
subtracts the no-op baseline.  Uploads are timed by (upload + tiny-reduce
fetch) minus the same baseline on resident data.

Run: python bench/profile_ops.py [B]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def t_med(f, n=5):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        f()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 21
    S = 1 << 20
    rng = np.random.default_rng(0)
    slots_np = (rng.zipf(1.1, size=B).astype(np.int64) % S).astype(np.int32)
    slots = jnp.asarray(slots_np)
    iota = jnp.arange(B, dtype=jnp.int32)
    state = jnp.zeros((S, 2), dtype=jnp.int32)
    rows = jnp.ones((B, 2), dtype=jnp.int32)
    mask = jnp.asarray(rng.random(B) < 0.5)
    print(f"B={B} S={S}", flush=True)

    csum = jax.jit(lambda x: x.sum()).lower(slots).compile()
    base = t_med(lambda: np.asarray(csum(slots)))
    print(f"  baseline (sum+8B fetch): {base*1000:.1f} ms", flush=True)

    # D2H fetch of B i32
    t = t_med(lambda: np.asarray(slots))
    print(f"  fetch {4*B>>20}MB: {t*1000:.1f} ms -> "
          f"{4*B/t/1e6:.0f} MB/s", flush=True)

    # H2D upload of B i32 (upload + sum fetch - baseline)
    t = t_med(lambda: np.asarray(csum(jnp.asarray(slots_np)))) - base
    print(f"  upload {4*B>>20}MB: {t*1000:.1f} ms -> "
          f"{4*B/max(t,1e-9)/1e6:.0f} MB/s", flush=True)

    def timed_op(name, fn, *args):
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        tc = time.perf_counter() - t0
        np.asarray(c(*args))
        t = t_med(lambda: np.asarray(c(*args))) - base
        print(f"  {name}: compile {tc:5.1f}s  run {t*1000:7.1f} ms", flush=True)

    timed_op("sort2", lambda s, i: jax.lax.sort(
        (s, i), num_keys=1, is_stable=True)[1].sum(), slots, iota)
    timed_op("gather_rows", lambda st, s: st[s].sum(), state, slots)
    timed_op("xla_scatter", lambda st, s, m, r: st.at[
        jnp.where(m, s, S)].set(r, mode="drop").sum(),
        state, slots, mask, rows)
    timed_op("elemwise10", lambda s: ((((s * 3 + 1) ^ 5) % 7 + s // 3)
                                      * 2 - 1).sum(), slots)
    timed_op("packbits", lambda m: jnp.packbits(m).sum(), mask)


if __name__ == "__main__":
    main()
