"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* configuration: the file named in its ``configs`` entry;
* traffic mix: ``bench/traffic/<traffic>.json``;
* metric reader: ``bench/metrics/<name>.py``; a dotted name with no file of
  its own (``filter_ms_per_query.sat``) uses the reader of the name before
  its last dot;
* roofline work counts: ``bench/roofline/<kernel>.py``;
* plain reference: ``bench/references/<reference>.py``, named by the
  configuration's ``reference`` key;
* device peaks: ``bench/peaks.json``, keyed by ``device_kind``.

An unknown name is an error, never a default.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = "bench"


class SpecError(LookupError):
    """A name in the benchmark has no file, or a file has no such entry."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Benchmark:
    def __init__(self, root):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.data = json.loads(path.read_text())
        self.bench = self.root / BENCH_DIR

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"unknown workload {name!r}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise SpecError(f"unknown configuration {name!r}")

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"unknown traffic mix {name!r}: no {path}")
        return json.loads(path.read_text())

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """``read(ctx) -> float | None`` of a metric."""
        name = metric
        while True:
            path = self.bench / "metrics" / f"{name}.py"
            if path.is_file():
                return _load_module(path, f"bench_metric_{name}").read
            if "." not in name:
                raise SpecError(f"no reader for metric {metric!r}")
            name = name.rsplit(".", 1)[0]

    def roofline(self, kernel: str):
        path = self.bench / "roofline" / f"{kernel}.py"
        if not path.is_file():
            raise SpecError(f"no work count for kernel {kernel!r}")
        return _load_module(path, f"bench_roofline_{kernel}")

    def reference(self, name: str):
        path = self.bench / "references" / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"unknown reference {name!r}")
        return _load_module(path, f"bench_reference_{name}")

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise SpecError(f"no peaks for device kind {device_kind!r}")
        return table["devices"][device_kind]
