"""Host<->device link probe.

One implementation shared by the bench harness (bench.py:link_probe) and
``TpuBatchedStorage.probe_link`` so the link numbers a run logs and the
profile the storage elects chunk plans from are measured identically —
same probe sizes, same rep counts, same arithmetic.

The probe jits a trivial reduction so each fetch is a full round trip
(on the pre-PR-1 remote link ``block_until_ready`` does not block; only fetches
prove completion — ROUND_NOTES).
"""

from __future__ import annotations

import time
from typing import Tuple

import numpy as np

PROBE_BYTES = 4 << 20  # 4 MiB upload probe


def measure_link(rtt_reps: int = 3, upload_reps: int = 2
                 ) -> Tuple[float, float, float]:
    """Measure (upload bytes/s, round-trip seconds, download bytes/s)
    with a tiny-fetch RTT probe, a 4 MiB upload probe, and a 4 MiB
    download probe (each shape compiled untimed first).  The two
    directions are probed SEPARATELY because the pre-PR-1 remote link degrades
    them independently (r5 observed 62 MB/s up against 5.3 MB/s down
    in one window) and the words-vs-digest election trades upload
    bytes against download bytes.  ~1-1.5 s on a healthy link; callers
    gate how often.  (A repeated ``np.asarray`` on one jax Array is
    served from its host cache, so each download rep fetches a
    DISTINCT device array.)"""
    import jax
    import jax.numpy as jnp

    csum = jax.jit(lambda v: v.sum())
    tiny = np.zeros(1024, dtype=np.int32)
    np.asarray(csum(jnp.asarray(tiny)))  # compile + settle
    t0 = time.perf_counter()
    for _ in range(rtt_reps):
        np.asarray(csum(jnp.asarray(tiny)))
    rtt_s = (time.perf_counter() - t0) / rtt_reps
    buf = np.random.default_rng(7).integers(
        0, 1 << 20, PROBE_BYTES // 4).astype(np.int32)
    np.asarray(csum(jnp.asarray(buf)))  # compile this shape untimed
    t0 = time.perf_counter()
    for _ in range(upload_reps):
        np.asarray(csum(jnp.asarray(buf)))
    up_s = max((time.perf_counter() - t0) / upload_reps - rtt_s, 1e-6)
    # Download: materialize distinct 4 MiB arrays on device (seeded from
    # a scalar upload — no upload traffic in the timed window), fetch
    # each once.
    fill = jax.jit(lambda s: jnp.full(PROBE_BYTES // 4, s, jnp.int32))
    handles = [fill(np.int32(i)) for i in range(upload_reps + 1)]
    np.asarray(handles[0])  # compile + settle
    t0 = time.perf_counter()
    for h in handles[1:]:
        np.asarray(h)
    down_s = max((time.perf_counter() - t0) / upload_reps - rtt_s, 1e-6)
    return PROBE_BYTES / up_s, rtt_s, PROBE_BYTES / down_s
