"""Find a cell and everything that belongs to it by name.

- the cell: ``BENCHMARK.json`` ``workloads`` entry;
- its configuration: ``benchmark/configs/<config>.json``;
- its traffic mix: ``benchmark/traffic/<traffic>.json``, whose
  ``driver`` names ``harness/<driver>.py``;
- its per-layer readers: ``benchmark/layer_metrics/<metric>.py``;
- the peaks: ``benchmark/peaks.json`` keyed by ``device_kind``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(RuntimeError):
    pass


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    config = _load_json(os.path.join(BENCH, "configs", w["config"] + ".json"))
    traffic = _load_json(os.path.join(BENCH, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def load_reader(metric: str):
    """The ``read(readings)`` function of one per-layer metric."""
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (have {sorted(table)})")
    return table[device_kind]
