"""The stream path on the profiler's clock: ``ratelimiter.stream.*``
spans (storage/tpu.py ``_span``) and the timers they feed.

A span is a ``jax.profiler.TraceAnnotation`` around the same interval
its stage timer records; under a profiler session a relay stream call
shows as one ``call`` span on the caller's thread with its stages as
children, and the walks, fetches and reconstructions of its chunks on
other threads, each tagged with its chunk's index."""

import glob
import os

import numpy as np
import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.storage import TpuBatchedStorage
from ratelimiter_tpu.storage.tpu import _RELAY_CHUNK

PREFIX = "ratelimiter.stream."
CALLER_STAGES = ("assign", "elect", "clear", "layout", "enqueue",
                 "drain_wait")


def _timer(st, stage):
    return st.registry.meters()[PREFIX + stage]


def test_span_records_its_timer_with_observability_on():
    st = TpuBatchedStorage(num_slots=1 << 12)
    try:
        before = _timer(st, "layout").count()
        with st._span("layout", 3) as sp:
            pass
        assert _timer(st, "layout").count() == before + 1
        assert sp.t1 >= sp.t0 and sp.secs >= 0.0
        # Stages without a timer are spans only.
        with st._span("elect"):
            pass
        assert PREFIX + "elect" not in st.registry.meters()
    finally:
        st.close()


def test_span_records_nothing_with_observability_off():
    st = TpuBatchedStorage(num_slots=1 << 12, observability=False)
    try:
        with st._span("fetch") as sp:
            pass
        assert sp.secs >= 0.0
        assert st.registry is None or not any(
            name.startswith(PREFIX) for name in st.registry.meters())
    finally:
        st.close()


def test_span_closes_and_records_when_the_body_raises(tmp_path):
    import jax
    from jax.profiler import ProfileData

    st = TpuBatchedStorage(num_slots=1 << 12)
    try:
        before = _timer(st, "decide").count()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with pytest.raises(RuntimeError, match="mid-stage"):
                with st._span("decide", 7) as sp:
                    raise RuntimeError("mid-stage")
        finally:
            jax.profiler.stop_trace()
        assert sp.t1 is not None
        assert _timer(st, "decide").count() == before + 1
        # end() after the block is a no-op: one record, one event.
        sp.end()
        assert _timer(st, "decide").count() == before + 1
        path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)[0]
        events = [ev for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name == PREFIX + "decide"]
        assert len(events) == 1
        assert dict(events[0].stats)["chunk"] == 7
    finally:
        st.close()


def _events(path):
    """``[(line key, name, start, end, chunk), ...]`` of the program's
    spans, one line key per host thread, and the set of programs the
    trace's op events name (``hlo_module``)."""
    from jax.profiler import ProfileData

    out, modules = [], set()
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
                if ev.name.startswith(PREFIX):
                    out.append(((p, k), ev.name[len(PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                stats.get("chunk")))
    return out, modules


def test_relay_stream_stages_nest_in_the_call_span(tmp_path):
    import jax

    now = [1_000_000]
    st = TpuBatchedStorage(num_slots=1 << 17, clock_ms=lambda: now[0])
    try:
        lid = st.register_limiter("tb", RateLimitConfig(
            max_permits=50, window_ms=120_000, refill_rate=10.0))
        rng = np.random.default_rng(5)
        n = _RELAY_CHUNK + (_RELAY_CHUNK >> 1)  # two chunks
        ids = ((rng.zipf(1.1, n) - 1) % (1 << 16)).astype(np.int64)
        st.acquire_stream_ids("tb", lid, ids)  # compiles every shape
        now[0] += 100
        assigned = _timer(st, "assign").count()
        jax.profiler.start_trace(str(tmp_path))
        try:
            st.acquire_stream_ids("tb", lid, ids)
        finally:
            jax.profiler.stop_trace()
        chunks = _timer(st, "assign").count() - assigned
        assert chunks >= 2
        path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)[0]
        events, modules = _events(path)
        # The device step is named for its function, not jit__unknown
        # (a bare functools.partial under jax.jit).
        assert "jit_tb_relay_counts" in modules, modules
        assert not any("unknown" in m for m in modules), modules
        calls = [e for e in events if e[1] == "call"]
        assert len(calls) == 1
        caller, _, c0, c1, _ = calls[0]

        def inside(e):
            return c0 <= e[2] and e[3] <= c1

        mine = [e for e in events if e[0] == caller and e[1] != "call"]
        assert all(inside(e) for e in mine)
        seen = {e[1] for e in mine}
        assert set(CALLER_STAGES) <= seen, seen
        # The caller's chunk indices: 0 .. chunks-1 on its assigns.
        assert sorted(e[4] for e in mine if e[1] == "assign") == \
            list(range(chunks))
        # Walks, fetches and reconstructions link to those chunks by
        # their metadata, inside the call: every later chunk's walk ran
        # on the prefetch worker, every fetch and decide on a drain.
        for stage in ("index", "fetch", "decide"):
            evs = [e for e in events if e[1] == stage]
            assert all(inside(e) for e in evs), stage
            assert sorted(e[4] for e in evs) == list(range(chunks)), stage
        elsewhere = {e[1] for e in events if e[0] != caller}
        assert {"index", "fetch", "decide"} <= elsewhere, elsewhere
        assert all(e[0] != caller for e in events
                   if e[1] == "index" and e[4] > 0)
    finally:
        st.close()
