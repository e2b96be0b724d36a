"""Closed-loop stream driver for a key space sharded over the chips of
one host: ``harness/stream.py``'s loop, calls and check, with the
storage built by the program's own sharded-storage factory
(``ratelimiter_tpu.storage.sharded_storage``) over the chips the cell
was given, one shard per chip.

At build the driver checks the placement: shard ``s``'s state lives on
``devices[s]``, and the devices are distinct; after the window, that
each shard's copy of the limiter table is on its device too.  Each
misplaced part (and a repeated device) counts in
``shard_placement_errors``, compared with the limit 0 beside the
reference's checks.  ``info.sorted_dispatch_share``
is the share of the window's shard dispatches that wrote their rows
with the tile sweep (``sharded|digest-sorted`` in the storage's
decision trace).  A program without the factory cannot run the cell:
the import fails before anything is built.
"""

from __future__ import annotations

from harness import stream

SORTED_PATH = "sharded|digest-sorted"


def placement_errors(engine, devices) -> int:
    """Shards whose state parts are not on their own device, plus one
    if two shards were given the same device."""
    want = [d.id for d in devices]
    errors = int(len(set(want)) != len(want))
    for parts in engine._parts.values():
        for s, part in enumerate(parts):
            errors += {d.id for d in part.devices()} != {want[s]}
    return errors


def table_placement_errors(engine, devices) -> int:
    """Shards whose copy of the limiter table (placed by their first
    dispatch) is not on their own device."""
    import jax

    want = [d.id for d in devices]
    return sum({d.id for leaf in jax.tree.leaves(tab)
                for d in leaf.devices()} != {want[s]}
               for s, tab in engine._table_parts[1].items())


class _WindowCount:
    """The limiter as the stream loop sees it, counting its calls, so
    that the storage's dispatch total is read where the window starts
    (after the prefill and warm-up calls)."""

    def __init__(self, limiter, storage, setup_calls: int):
        self._limiter = limiter
        self._storage = storage
        self._setup_calls = setup_calls
        self._calls = 0
        self.start_total = None

    def try_acquire_stream_ids(self, ids, **kw):
        if self._calls == self._setup_calls:
            self.start_total = self._storage.trace.snapshot(
                last=1)["total_dispatches"]
        self._calls += 1
        return self._limiter.try_acquire_stream_ids(ids, **kw)


def sorted_share(storage, start_total: int):
    """Share of the dispatches since ``start_total`` that took the
    sorted row write; past the trace ring's capacity, of its newest
    records."""
    if start_total is None:
        return None
    snap = storage.trace.snapshot(last=1 << 30)
    n = snap["total_dispatches"] - start_total
    recent = snap["recent"][-n:] if n > 0 else []
    if not recent:
        return None
    return sum(r.get("path") == SORTED_PATH for r in recent) / len(recent)


def run(cell, seed: int, seconds: float, trace: bool, devices,
        process_start: float):
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import TokenBucketRateLimiter
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import sharded_storage

    config, traffic = cell.config, cell.traffic
    if config["algorithm"] != "token_bucket":
        raise ValueError("the sharded driver runs token-bucket cells")
    if len(devices) != config["shards"]:
        raise ValueError(f"{config['shards']} shards need as many chips, "
                         f"got {len(devices)}")
    built = {}

    def build(config, clock):
        storage = sharded_storage(config["slots"], devices,
                                  clock_ms=lambda: clock[0])
        sps = storage.engine.slots_per_shard
        if sps != config["slots_per_shard"]:
            raise ValueError(f"the factory gave {sps} slots per shard, the "
                             f"configuration states "
                             f"{config['slots_per_shard']}")
        limiter = TokenBucketRateLimiter(
            storage, RateLimitConfig(**config["policy"]), MeterRegistry())
        built["errors"] = placement_errors(storage.engine, devices)
        built["storage"] = storage
        built["counted"] = _WindowCount(
            limiter, storage,
            traffic["warmup_calls"] + bool(traffic.get("prefill")))
        return storage, built["counted"]

    out = stream.run(cell, seed, seconds, trace, devices, process_start,
                     build=build)
    storage = built["storage"]
    errors = built["errors"] + table_placement_errors(storage.engine,
                                                      devices)
    out.readings.driver = "stream_sharded"
    out.checks["shard_placement_errors"] = {"value": errors, "limit": 0}
    out.correct = out.correct and errors == 0
    out.info["sorted_dispatch_share"] = sorted_share(
        storage, built["counted"].start_total)
    return out
