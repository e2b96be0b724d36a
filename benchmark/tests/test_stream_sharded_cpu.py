"""The sharded stream driver end to end on four CPU devices at a tiny
size: the window's decisions check out against the reference, every
shard's state sits on its own device, the tile sweep (interpret mode)
serves the shard dispatches, and every per-layer reader of the cell
returns a number on a trace in which XLA's CPU threads stand in for the
chips' op lines.

The cell runs in a child process: the four CPU devices must be asked
for before JAX starts."""

import json
import os
import subprocess
import sys

from harness import spec

CHILD = r"""
import json, re, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from harness import spec, trace
from run import result_line, run_cell
import ratelimiter_tpu.storage.tpu as tpu_mod
from ratelimiter_tpu.ops.pallas import block_scatter

trace.DEVICE_PLANE = re.compile(r"^/host:CPU$")
trace.OPS_LINE = re.compile(r"^tf_XLA")
block_scatter._INTERPRET = True
block_scatter._probe_ok = None
tpu_mod._SORT_UNIQUES_MIN = 256
cell = spec.load_cell("tb-burst-1m-zipf.stream-4chip")
cell.config["keys"]["count"] = 4000
cell.config.update(slots=5120, slots_per_shard=1280)
cell.traffic.update(ids_per_call=1 << 15, batch=1 << 13, subbatches=2,
                    warmup_calls=2)
out = run_cell(cell, 12345678901, 3.0, True, jax.devices()[:4],
               time.monotonic())
line = result_line(cell, out, True, {"hbm_bytes_per_s": 819e9})
print(json.dumps({"line": line,
                  "per_layer": [m["name"] for m in cell.per_layer]}))
"""


def test_sharded_stream_cell_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, spec.BENCH, spec.ROOT],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=spec.ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    line = got["line"]
    assert line["correct"], line["checks"]
    assert line["checks"]["shard_placement_errors"] == {"value": 0,
                                                        "limit": 0}
    assert line["checks"]["mismatches"]["value"] == 0
    assert line["device"]["count"] == 4
    assert line["info"]["sorted_dispatch_share"] >= 0.95
    assert line["info"]["decisions_compared"] > 0
    assert set(line["metrics"]) == set(got["per_layer"]) == {
        "sharded.route_ns_per_decision",
        "sharded.straggle_ns_per_decision",
        "device_idle_share.stream-4chip", "step_roofline.stream-4chip"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
