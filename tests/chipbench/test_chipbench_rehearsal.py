"""CPU rehearsals of every cell: the whole command, at tiny sizes, prints
a last line of the result's shape that names the CPU; without the
rehearsal switch it refuses to run."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
TOP_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(root, cell, *extra, trace=0, cache_dir, chips=1, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)  # the command finds src/ beside itself
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", "2147483659",
           "--seconds", "1", "--trace", str(trace), *extra]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(cell, repo_root, tmp_path):
    w = spec.cell(BENCH, cell)
    line = last_line(run_cell(repo_root, cell, "--cpu-rehearsal", cache_dir=tmp_path,
                              chips=w["chips"]))
    assert list(line)[: len(TOP_KEYS)] == TOP_KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == w["chips"]
    e2e, _ = spec.metrics_for(BENCH, cell)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert "setup_s" in line["metrics"]
    for m in e2e:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert line["checks"] == {"bad_answers": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_no_device_metric_off_the_chip(cell, repo_root, tmp_path):
    w = spec.cell(BENCH, cell)
    line = last_line(run_cell(repo_root, cell, "--cpu-rehearsal", trace=1, cache_dir=tmp_path,
                              chips=w["chips"]))
    assert line["correct"] is True
    _, per_layer = spec.metrics_for(BENCH, cell)
    sources = {m["name"]: m["source"] for m in per_layer}
    if any(s != "device_trace" for s in sources.values()):
        assert line["metrics"], "a counter metric should be read off the chip too"
    for name in line["metrics"]:
        assert sources[name] != "device_trace", name
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_no_tpu_means_no_run(repo_root, tmp_path):
    proc = run_cell(repo_root, "sort_60mb_random", cache_dir=tmp_path)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_mesh_cell_rehearsal_prints_the_result_line(mesh_root, tmp_path):
    line = last_line(run_cell(mesh_root, "dist_sort_60mb_mesh4", "--cpu-rehearsal",
                              cache_dir=tmp_path, chips=4))
    assert line["correct"] is True and line["device"]["count"] == 4
    assert set(line["metrics"]) == {"sort_keys_per_s", "setup_s"}


def test_too_few_chips_means_no_run(mesh_root, tmp_path):
    proc = run_cell(mesh_root, "dist_sort_60mb_mesh4", "--cpu-rehearsal", cache_dir=tmp_path)
    assert proc.returncode == 3 and proc.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(repo_root, tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has no
    system to measure: the run fails and prints no result."""
    work = tmp_path / "alone"
    work.mkdir()
    shutil.copy(repo_root / "BENCHMARK.json", work)
    for p in BENCH["paths"]:
        shutil.copytree(repo_root / p, work / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(work, "sort_60mb_random", "--cpu-rehearsal", cache_dir=tmp_path / "c")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
