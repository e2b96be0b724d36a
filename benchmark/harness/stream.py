"""Closed-loop stream driver: one caller hands the limiter whole
streams of integer key ids through ``try_acquire_stream_ids``.

Set-up draws the run's distinct calls from the seed (enough that no
call of the window repeats one before it), builds the storage and
limiter at the configuration's size, runs the prefill call (if the
traffic has one) and the warm-up calls, which compile and warm every
shape the window uses.  The window then makes call after call until
``--seconds`` have passed; the clock the storage reads advances
``clock_step_ms`` per call.  Throughput is all
decisions of the window over its wall time, which ends with the last
call.  After the window (and after the device's peak memory is read
and the storage closed) every call is replayed through the reference
(``check_stream.py``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from harness import device, gen
from harness.check_stream import StreamCheck
from harness.outcome import Outcome
from harness.readings import Readings, snapshot_timers, timer_deltas
from harness.work import stream_call_bytes

# The injected clock at the first window call: a minute boundary, so a
# sliding window's prefill (``before_window_ms`` earlier) lands in the
# previous window.
T0_MS = 1_760_000_040_000
TRACE_SECONDS = 3.0


def build_limiter(config: dict, clock):
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import (
        SlidingWindowRateLimiter,
        TokenBucketRateLimiter,
    )
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    storage = TpuBatchedStorage(num_slots=config["slots"],
                                clock_ms=lambda: clock[0])
    cls = {"token_bucket": TokenBucketRateLimiter,
           "sliding_window": SlidingWindowRateLimiter}[config["algorithm"]]
    limiter = cls(storage, RateLimitConfig(**config["policy"]),
                  MeterRegistry())
    return storage, limiter


def distinct_calls(traffic: dict, seconds: float) -> int:
    """How many distinct calls set-up draws: the warm-up's and enough
    for a window of ``seconds`` at ``distinct_calls_per_s``, so that no
    call of the window repeats an earlier one."""
    return traffic["warmup_calls"] + math.ceil(
        seconds * traffic["distinct_calls_per_s"])


def plan(traffic: dict, n_calls: int):
    """The calls of a run in order, as ``(call, now_ms)``: the prefill
    (if any), the warm-up calls, then the window's calls without end
    (past ``n_calls`` they would start again from the first)."""
    prefill = traffic.get("prefill")
    if prefill:
        yield "prefill", T0_MS - prefill["before_window_ms"]
    i = 0
    while True:
        yield i % n_calls, T0_MS + traffic["clock_step_ms"] * i
        i += 1


def call_store(seed: int, config: dict, traffic: dict,
               n_calls: int) -> dict:
    """Every distinct call's key ids, drawn from the seed."""
    store = dict(enumerate(gen.stream_calls(
        seed, config["keys"], traffic["ids_per_call"], n_calls)))
    if traffic.get("prefill"):
        store["prefill"] = gen.prefill_ids(seed, config["keys"],
                                           traffic["prefill"])
    return store


def run(cell, seed: int, seconds: float, trace: bool, devices,
        process_start: float, build=build_limiter) -> Outcome:
    config, traffic = cell.config, cell.traffic
    n_ids = traffic["ids_per_call"]
    kw = {"batch": traffic["batch"], "subbatches": traffic["subbatches"]}
    clock = [T0_MS]
    n_calls = distinct_calls(traffic, seconds)
    with device.annotate("bench.gen"):
        store = call_store(seed, config, traffic, n_calls)
    storage, limiter = build(config, clock)
    calls = []
    todo = plan(traffic, n_calls)

    def call():
        key, now = next(todo)
        clock[0] = now
        with device.annotate("bench.stream_call"):
            got = limiter.try_acquire_stream_ids(store[key], **kw)
        calls.append((key, now, got))

    compiles = device.Compiles()
    if traffic.get("prefill"):
        with device.annotate("bench.prefill"):
            call()
    with device.annotate("bench.warmup"):
        for _ in range(traffic["warmup_calls"]):
            call()
    profiler = device.Profiler() if trace else None
    if profiler:
        profiler.start()
    before = snapshot_timers(storage.registry)
    compiles_before = compiles.n
    setup_s = time.monotonic() - process_start
    first = len(calls)
    t0 = time.perf_counter()
    if profiler:
        with device.annotate("bench.trace_window"):
            while time.perf_counter() - t0 < min(TRACE_SECONDS, seconds):
                call()
        profiler.stop()
    traced = len(calls) - first
    while time.perf_counter() - t0 < seconds:
        call()
    wall = time.perf_counter() - t0
    after = snapshot_timers(storage.registry)
    compiles_in_window = compiles.n - compiles_before
    decisions = (len(calls) - first) * n_ids
    allowed = sum(int(np.count_nonzero(got)) for _, _, got in calls[first:])
    record = device.device_record(devices)
    storage.close()
    del storage, limiter

    readings = Readings(driver="stream", algorithm=config["algorithm"],
                        decisions=decisions,
                        timers=timer_deltas(before, after), peaks={})
    check = StreamCheck(config, seed)
    t_check = time.perf_counter()
    with device.annotate("bench.check"):
        check.replay((store[key], now, got) for key, now, got in calls)
    check.close()
    check_s = time.perf_counter() - t_check

    breakdown = None
    if profiler:
        red = profiler.reduce()
        readings.trace = red
        readings.work_bytes = sum(
            stream_call_bytes(config["algorithm"], n_ids, u)
            for u in check.uniques[first:first + traced])
        record.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.device_ops,
                     "idle_gaps": red.idle_gaps}

    info = {"setup_s": setup_s, "window_calls": len(calls) - first,
            "distinct_calls": n_calls,
            "repeated_calls": max(0, len(calls) - first
                                  - (n_calls - traffic["warmup_calls"])),
            "denied_share": 1 - allowed / decisions,
            "compiles_in_window": compiles_in_window,
            "check_s": check_s, **check.info()}
    return Outcome(correct=check.correct, attempted=decisions, failed=0,
                   end_to_end={"stream_decisions_per_s": decisions / wall,
                               "setup_s": setup_s},
                   readings=readings, device=record, checks=check.checks(),
                   info=info, breakdown=breakdown)
