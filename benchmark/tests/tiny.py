"""Tiny cells for CPU tests: the real drivers and checks, at sizes a
test run holds, with the harness's look for a chip skipped.

A cell here is a configuration file and a traffic file of the
benchmark, cut to size."""

from __future__ import annotations

import copy
import json
import os
import time

from harness import spec

CELLS = {
    "tb-burst-1m-zipf.stream": ("tb-burst-1m-zipf", "stream-2m"),
    "sw-api-10m-uniform.stream": ("sw-api-10m-uniform",
                                  "stream-1m-prefilled"),
}


def _json(*parts):
    with open(os.path.join(spec.BENCH, *parts)) as f:
        return json.load(f)


def tiny_cell(name: str, keys: int = 4000, ids: int = 2048) -> spec.Cell:
    """Cell ``name`` cut to ``keys`` keys and ``ids`` requests per call."""
    config_name, traffic_name = CELLS[name]
    config = copy.deepcopy(_json("configs", config_name + ".json"))
    traffic = copy.deepcopy(_json("traffic", traffic_name + ".json"))
    config["keys"]["count"] = keys
    config["slots"] = -(-int(keys * 1.25) // 256) * 256
    traffic.update(ids_per_call=ids, batch=ids // 4, subbatches=2,
                   warmup_calls=2)
    bench = _json("..", "BENCHMARK.json")
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())]
    return spec.Cell(name, 1, config, traffic, [], per_layer)


def run_tiny(cell, seed=12345678901, seconds=1.0, trace=False, **kw):
    import jax

    from run import run_cell

    return run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                    time.monotonic(), **kw)
