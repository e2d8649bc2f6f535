"""A cell and every file it is made of, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
rest follows from the names:

- ``bench/configs/<config>.json``   the model and its KV pool;
- ``bench/traffic/<traffic>.json``  the mix, which names its generator
  module (``bench/traffic/<generator>.py``);
- ``bench/limits/<cell>.json``      the correctness limit and the
  readings it was set from;
- ``bench/metrics/<metric>.py``     one reader per metric; a name with
  a suffix and no file of its own (``decode_cycle_ms.closed``) is read by
  the reader of the name before the suffix.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    spec: dict
    limits: dict
    generator: object
    #: metric name -> (BENCHMARK.json entry, reader module), in the order
    #: BENCHMARK.json lists them
    end_to_end: Dict[str, tuple]
    per_layer: Dict[str, tuple]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod           # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def reader_path(root: str, name: str) -> str:
    """``bench/metrics/<name>.py``, or that of ``name`` less its last
    ``.suffix`` where the name has no file of its own."""
    base = name
    while True:
        path = os.path.join(root, "bench", "metrics", base + ".py")
        if os.path.exists(path) or "." not in base:
            return path
        base = base.rsplit(".", 1)[0]


def _metrics(root: str, entries: List[dict], cell: str) -> Dict[str, tuple]:
    out = {}
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out[m["name"]] = (m, load_module(reader_path(root, m["name"]),
                                         "metric_" + m["name"]))
    return out


def load(root: str, name: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = _json(os.path.join(root, "bench", "configs", w["config"] + ".json"))
    spec = _json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    limits = _json(os.path.join(root, "bench", "limits", name + ".json"))
    gen = load_module(os.path.join(root, "bench", "traffic",
                                   spec["generator"] + ".py"),
                      "traffic_" + spec["generator"])
    return Cell(name, w["chips"], conf, spec, limits, gen,
                _metrics(root, bench["end_to_end"], name),
                _metrics(root, bench["per_layer"], name))
