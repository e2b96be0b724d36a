"""Characterize H2D upload cost (written for the remote link used before
PR 1; not yet run on an attached chip): size scaling, API
variants, dtype, and concurrency.  Completion is forced by fetching an
8-byte reduction of the uploaded buffer.

Run: python bench/profile_upload.py
"""

from __future__ import annotations

import concurrent.futures as cf
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    csum = {}

    def force(x):
        n = x.size * x.dtype.itemsize
        key = (x.shape, str(x.dtype))
        if key not in csum:
            csum[key] = jax.jit(lambda v: v.astype(jnp.int32).sum()).lower(
                jax.ShapeDtypeStruct(x.shape, x.dtype)).compile()
        return np.asarray(csum[key](x)), n

    # RTT baseline: resident array reduce+fetch
    res = jnp.zeros(1024, jnp.int32)
    force(res)
    t0 = time.perf_counter()
    for _ in range(5):
        force(res)
    rtt = (time.perf_counter() - t0) / 5
    print(f"rtt floor: {rtt*1000:.0f} ms", flush=True)

    def t_upload(name, make, n_rep=3):
        ts = []
        for _ in range(n_rep):
            arr = make()
            t0 = time.perf_counter()
            x = jnp.asarray(arr) if not isinstance(arr, jnp.ndarray) else arr
            _, nbytes = force(x)
            ts.append(time.perf_counter() - t0)
        t = sorted(ts)[len(ts) // 2] - rtt
        print(f"  {name}: {t*1000:7.0f} ms  "
              f"{nbytes/max(t,1e-9)/1e6:8.1f} MB/s", flush=True)

    for mb in (1, 4, 16):
        n = mb << 20
        print(f"upload {mb} MB:", flush=True)
        t_upload("asarray_i32",
                 lambda n=n: rng.integers(0, 1 << 20, n // 4).astype(np.int32))
        t_upload("device_put_i32",
                 lambda n=n: jax.device_put(
                     rng.integers(0, 1 << 20, n // 4).astype(np.int32), dev))
        t_upload("asarray_u8",
                 lambda n=n: rng.integers(0, 255, n).astype(np.uint8))
        t_upload("zeros_i32 (compressible?)",
                 lambda n=n: np.zeros(n // 4, dtype=np.int32))

    # concurrency: 4 parallel 4MB uploads
    print("4 x 4MB parallel uploads:", flush=True)
    arrs = [rng.integers(0, 1 << 20, 1 << 20).astype(np.int32)
            for _ in range(4)]
    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(4) as ex:
        handles = list(ex.map(lambda a: jnp.asarray(a), arrs))
    for h in handles:
        force(h)
    t = time.perf_counter() - t0
    print(f"  total {t*1000:.0f} ms -> {16/max(t,1e-9):.1f} MB/s aggregate",
          flush=True)


if __name__ == "__main__":
    main()
