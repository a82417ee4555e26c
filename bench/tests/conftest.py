"""Shared set-up of the harness tests: the bench modules and the system
under test on the path, JAX on the CPU, and a tiny benchmark tree."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DATASET = {"n_vertices": 300, "n_edges": 1500, "n_labels": 8,
                "label_dist": "uniform", "n_edge_labels": 1,
                "degrees": "power_law", "exponent": 2.5, "seed": 5}
TINY_POOL = {"sizes": [3, 5], "per_size": 4, "seed": 6}
# the harness's update path, which no cell of BENCHMARK.json uses yet
CHURN_E2E = {"name": "update_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["hprd.churn"]}
CHURN_LAYER = {"name": "mutation_ms_p95", "unit": "ms", "better": "lower",
               "source": "host_clock", "layer": "index maintenance",
               "moves": "update_p95_ms", "workloads": ["hprd.churn"]}


def make_tiny_root(tmp: Path) -> Path:
    """A copy of the benchmark tree whose cells run in seconds on the CPU:
    the real harness, readers and reference, a 300-vertex graph, and the
    real cell names bound to tiny mixes."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((BENCH / "configs" / "hprd.json").read_text())
    cfg["dataset"] = TINY_DATASET
    cfg["store"]["degree_cap"] = 256
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mixes = {
        "tiny_read": {"rate": 10, "pool": TINY_POOL, "max_embeddings": 1000,
                      "updates": None},
        "tiny_churn": {"rate": 10, "pool": TINY_POOL, "max_embeddings": 1000,
                       "updates": {"rate": 3, "edges": 8}},
    }
    for name, mix in mixes.items():
        (tmp / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # the CPU gives no device trace: those readers have nothing to read
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["source"] != "device_trace"]
    # the churn cell reports what the read cell does, and the index
    # maintenance that its update stream exercises
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "hprd.read" in m.get("workloads", ()):
            m["workloads"].append("hprd.churn")
    spec["end_to_end"].append(CHURN_E2E)
    spec["per_layer"].append(CHURN_LAYER)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [
        {"name": "hprd.read", "config": "tiny", "traffic": "tiny_read",
         "chips": 1, "why": "test"},
        {"name": "hprd.churn", "config": "tiny", "traffic": "tiny_churn",
         "chips": 1, "why": "test"},
    ]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))
