"""A configuration, a traffic mix, a loop, a key distribution and a
per-layer metric are added by new files and ``BENCHMARK.json`` entries
alone: in a copy of the benchmark, the harness finds each by name and runs
the new cell, and no file that was already there changes."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

NEW_CONFIG = {
    "name": "tiny_sort", "system": "sort_engine", "topology": {"d_h": 1, "variant": "half"},
    "mesh": None, "n": 50000, "dtype": "int32", "reduced": [], "rehearsal": {"n": 5000},
}
# a mix of an existing loop, and one of a new loop over a new key distribution
NEW_TRAFFIC = {"kind": "sort_loop", "keys": {"distribution": "random"}, "arrays": 2}
NEW_KIND_TRAFFIC = {"kind": "prefix_sorts", "keys": {"distribution": "few_values", "values": 3},
                    "arrays": 2, "prefixes": [0.25, 1.0], "rehearsal": {"arrays": 1}}
NEW_READER = '''
def read(run):
    return float(run.counters["calls"])
'''
NEW_DISTRIBUTION = '''
def draw(rng, n, dtype, values):
    return rng.integers(0, values, n).astype(dtype)
'''
NEW_LOOP = '''
import time

from chipbench import generate as gen
from chipbench.drivers import Driver, build_engine


class Loop(Driver):
    """Sorts a prefix of each array, cycling through the prefix shares."""

    def setup(self):
        tr = self.traffic
        self.engine = build_engine(self.config, self.devices)
        arrays = gen.sort_arrays(self.config["n"], tr["arrays"], self.config["dtype"],
                                 tr["keys"], self.seed, self.root)
        self.inputs = [a[: max(2, int(a.size * p))] for a in arrays for p in tr["prefixes"]]
        for x in self.inputs:
            self.engine.sort(x)

    def window(self):
        self.calls = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.seconds:
            x = self.inputs[len(self.calls) % len(self.inputs)]
            self.calls.append((x, self.engine.sort(x)))
        self.elapsed = time.perf_counter() - t0
        self.attempted = len(self.calls)
        self.counters = {"calls": len(self.calls)}

    def end_to_end(self):
        keys = sum(x.size for x, _ in self.calls)
        return {"sort_keys_per_s": keys / self.elapsed / 1e6}

    def close(self):
        del self.engine

    def answers(self):
        return iter(self.calls)
'''


NEW_CELLS = {"tiny_sort.pair": "random_pair", "tiny_sort.prefixes": "few_values_prefixes"}


def digests(root):
    return {
        p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts
    }


@pytest.fixture
def copy_with_additions(repo_root, tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(repo_root / "BENCHMARK.json", root)
    shutil.copytree(repo_root / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(repo_root / "src")
    before = digests(root / "chipbench")

    (root / "chipbench/configs/tiny_sort.json").write_text(json.dumps(NEW_CONFIG))
    (root / "chipbench/traffic/random_pair.json").write_text(json.dumps(NEW_TRAFFIC))
    (root / "chipbench/traffic/few_values_prefixes.json").write_text(json.dumps(NEW_KIND_TRAFFIC))
    (root / "chipbench/metrics/calls_in_window.py").write_text(NEW_READER)
    (root / "chipbench/keys/few_values.py").write_text(NEW_DISTRIBUTION)
    (root / "chipbench/loops/prefix_sorts.py").write_text(NEW_LOOP)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_sort", "source": "test",
                             "file": "chipbench/configs/tiny_sort.json",
                             "reduced": [], "why": "test"})
    for name, traffic in NEW_CELLS.items():
        bench["workloads"].append({"name": name, "config": "tiny_sort",
                                   "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "sort_keys_per_s":
            m["workloads"] += list(NEW_CELLS)
    bench["per_layer"].append({"name": "calls_in_window", "unit": "calls", "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "sort_keys_per_s", "workloads": list(NEW_CELLS)})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def test_new_files_are_found_by_name(copy_with_additions):
    root, before = copy_with_additions
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, "tiny_sort.pair")
    assert spec.rehearsal(spec.config(bench, cell["config"], root))["n"] == 5000
    assert spec.traffic(cell["traffic"], root)["arrays"] == 2
    assert spec.loop("prefix_sorts", root).__name__ == "Loop"
    assert spec.load_named("keys", "few_values", root).draw
    e2e, layer = spec.metrics_for(bench, "tiny_sort.pair")
    assert [m["name"] for m in layer] == ["calls_in_window"]
    assert {m["name"] for m in e2e} == {"sort_keys_per_s", "setup_s"}

    class Run:
        counters = {"calls": 7}

    assert spec.reader("calls_in_window", root)(Run) == 7.0
    after = digests(root / "chipbench")
    assert {k: after[k] for k in before} == before


def test_variant_metric_falls_back_to_its_base_reader(repo_root):
    assert spec.reader("device_idle_share.sort", repo_root) is not None
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.x", repo_root)


@pytest.mark.parametrize("cell", list(NEW_CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_runs(copy_with_additions, tmp_path, trace, cell):
    root, _ = copy_with_additions
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", "9",
         "--seconds", "0.5", "--trace", str(trace), "--cpu-rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    want = {"calls_in_window"} if trace else {"sort_keys_per_s", "setup_s"}
    assert set(line["metrics"]) == want
