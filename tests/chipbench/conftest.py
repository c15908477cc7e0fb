"""Make the benchmark's package importable from its tests, and give them
the checkout's root."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="session")
def repo_root():
    return ROOT


MESH_CONFIG = {"name": "paper_sort_60mb_mesh4", "source": "https://arxiv.org/abs/2109.05176",
               "file": "chipbench/configs/paper_sort_60mb_mesh4.json", "reduced": [],
               "why": "the 60 MB sort over a 4-chip mesh"}
MESH_CELL = {"name": "dist_sort_60mb_mesh4", "config": "paper_sort_60mb_mesh4",
             "traffic": "random", "chips": 4, "why": "the exchange exists only across chips"}


@pytest.fixture
def mesh_root(tmp_path):
    """A checkout whose benchmark also holds the four-chip mesh cell, its
    configuration and its collective reader, which wait for a chip run
    (PERF.md, Open questions)."""
    root = tmp_path / "mesh_checkout"
    root.mkdir()
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURES / "paper_sort_60mb_mesh4.json", root / "chipbench" / "configs")
    shutil.copy(FIXTURES / "dist_collective_share.py", root / "chipbench" / "metrics")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(MESH_CONFIG)
    bench["workloads"].append(MESH_CELL)
    for m in bench["end_to_end"]:
        if m["name"] == "sort_keys_per_s":
            m["workloads"].append(MESH_CELL["name"])
    bench["per_layer"].append({"name": "dist_collective_share", "unit": "%", "better": "lower",
                               "source": "device_trace", "layer": "dist exchange (core/dist_sort.py)",
                               "moves": "sort_keys_per_s", "workloads": [MESH_CELL["name"]]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "src").symlink_to(ROOT / "src")
    return root
