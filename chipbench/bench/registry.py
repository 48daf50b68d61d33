"""Find a cell's configuration, layout, traffic mix, peaks and metric readers
by name.

Everything that belongs to one configuration, one architecture, one traffic
mix or one metric sits in a file of its own; adding one needs only new files
and their entries in ``BENCHMARK.json``:

- ``configs/<config>.json``: the file that ``BENCHMARK.json`` names, with
  the published ``config.json``'s keys; its ``quantization_config``, where
  it has one, says how the weights are stored (``bench/spec.py``);
- ``layouts/<model_type>.py``: ``layer_tensors(cfg) -> List[Tensor]``, the
  tensors of the configuration's decoder layers in Hugging Face naming and
  module order, found by the configuration's ``model_type``;
- ``traffic/<traffic>.json``: the parameters the general generator reads;
- ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def load_benchmark(path: str = os.path.join(REPO, "BENCHMARK.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: Dict, name: str) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            with open(os.path.join(REPO, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(BENCH_DIR, "bench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def metrics_for(bm: Dict, cell_name: str, traced: bool) -> List[Dict]:
    """The end-to-end metrics (untraced) or per-layer metrics (traced) that
    ``cell_name`` reports."""
    group = bm["per_layer"] if traced else bm["end_to_end"]
    return [m for m in group if cell_name in m.get("workloads", [cell_name])]


def _module(kind: str, name: str):
    """The module ``<kind>/<name>.py``; a missing file is an error that
    names it."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    """The ``read`` function of ``metrics/<metric_name>.py``."""
    return _module("metrics", metric_name).read


def layout(model_type: str):
    """The ``layer_tensors`` function of ``layouts/<model_type>.py``."""
    return _module("layouts", model_type).layer_tensors
