"""Where the harness finds each piece, by the names in ``BENCHMARK.json``.

* ``workloads/<cell>.json``: the cell's configuration name, driver kind,
  traffic parameters and the limits of its correctness check;
* ``configs/<config>.json``: the configuration as it is run, and
  ``configs/<config>.ref.py`` beside it, its plain reference;
* ``drivers/<kind>.py``: one general driver per kind of work;
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``peaks.json``: the chip's peaks by ``device_kind``.

A new cell, configuration or metric is a new file here plus its entry in
``BENCHMARK.json``; nothing that exists is edited.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> Dict:
    """The ``BENCHMARK.json`` entry of a cell; KeyError when unknown."""
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def workload(name: str) -> Dict:
    return read_json(os.path.join(HERE, "workloads", f"{name}.json"))


def config(name: str) -> Dict:
    return read_json(os.path.join(HERE, "configs", f"{name}.json"))


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path (its name may hold
    characters that a module name may not)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str):
    """The plain reference module beside a configuration."""
    return load_module(os.path.join(HERE, "configs", f"{config_name}.ref.py"),
                       f"chip_ref_{config_name.replace('-', '_')}")


def driver(kind: str):
    """The general driver of a kind of work (``drivers/<kind>.py``)."""
    if not os.path.isfile(os.path.join(HERE, "drivers", f"{kind}.py")):
        raise KeyError(f"no driver drivers/{kind}.py")
    return importlib.import_module(f"benchmarks.chip.drivers.{kind}")


def metric_reader(name: str):
    """The reader module of a per-layer metric (``read(run) -> float or
    None``)."""
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                       "chip_metric_" + name.replace(".", "_")
                       .replace("-", "_"))


def metrics_for(cell_name: str, section: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those that
    list it under ``workloads``, and those without that key."""
    out = []
    for m in benchmark()[section]:
        cells = m.get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; KeyError for a device not in the table."""
    table = read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in peaks.json")
    p = table[device_kind]
    return {"ops_per_s": p["bf16_flops_per_s"],
            "hbm_bytes_per_s": p["hbm_bytes_per_s"]}
