"""``BENCHMARK.json`` and the files it names, found by name.

* a cell (``workloads`` entry) names a configuration and a traffic mix;
* configuration ``c`` is the file its entry gives (``bench/configs/c.json``),
  whose ``data`` names the generator and reference module
  ``bench/data/<data>.py``;
* traffic mix ``t`` is ``bench/traffic/t.json``;
* metric ``m`` (end-to-end or per-layer) is read by
  ``bench/metrics/<m>.py``, a module with ``read(run) -> float | None``.

So a cell, a configuration, a traffic mix or a metric is added by adding
files and entries, without editing a file that is there.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


class Cell:
    def __init__(self, manifest: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config_entry = configs[self.spec["config"]]
        with open(root / self.config_entry["file"]) as f:
            self.config = json.load(f)
        with open(root / "bench" / "traffic" /
                  f"{self.spec['traffic']}.json") as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def data_module(self):
        return importlib.import_module(f"bench.data.{self.config['data']}")

    def metrics(self, traced: bool) -> list:
        return self.per_layer if traced else self.end_to_end


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def limits(root: Path = ROOT) -> dict:
    with open(root / "bench" / "limits.json") as f:
        return {k: v["limit"] for k, v in json.load(f).items()}
