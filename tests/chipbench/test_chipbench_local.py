"""The cell ``sort_60mb_local``: its entries in the real ``BENCHMARK.json``,
its keys, the loop's balance counter, its reader, and a CPU rehearsal that
runs the sampled sim path."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import generate as gen
from chipbench import spec
from chipbench.run import Run

CELL = "sort_60mb_local"
PLANNING = "engine planning and capacity (core/engine.py)"
STAGING = "engine host staging (core/engine.py)"
KERNELS = "kernels (device executables of core/engine.py)"
# name: (source, layer, unit), in BENCHMARK.json's order
METRICS = {
    "bucket_imbalance": ("program_counter", PLANNING, "x"),
    "sim_sort_device_ms.local": ("device_trace", KERNELS, "ms"),
    "sim_sort_roofline.local": ("device_trace", KERNELS, "%"),
    "device_idle_share.local": ("device_trace", "device", "%"),
    "sort_pad_share.local": ("program_counter", PLANNING, "%"),
    "sort_pad_ms.local": ("program_span", STAGING, "ms"),
    "sort_h2d_ms.local": ("program_span", STAGING, "ms"),
    "sort_d2h_ms.local": ("program_span", STAGING, "ms"),
    "sort_dispatch_idle_ms.local": ("device_trace", "device", "ms"),
}
LOCAL = {"distribution": "local"}
SEEDS = (3, 2**31 + 5, 2**33 + 1)


# ------------------------------------------------------------ the entries
def test_the_cell_reports_exactly_its_metrics():
    bench = spec.load_benchmark()
    e2e, layer = spec.metrics_for(bench, CELL)
    assert [m["name"] for m in e2e] == ["sort_keys_per_s", "setup_s"]
    assert [m["name"] for m in layer] == list(METRICS)
    for m in layer:
        assert (m["source"], m["layer"], m["unit"]) == METRICS[m["name"]], m["name"]
        assert m["moves"] == "sort_keys_per_s" and m["workloads"] == [CELL]
        assert spec.reader(m["name"]) is not None


def test_the_cell_and_its_configuration():
    bench = spec.load_benchmark()
    cell = spec.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("paper_sort_60mb_local", "local", 1)
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"].startswith("https://arxiv.org/abs/2109.05176 Sec. 5-6: ")
    assert (entry["file"], entry["reduced"]) == ("chipbench/configs/paper_sort_60mb_local.json", [])
    assert entry["source"] not in [c["source"] for c in bench["configs"] if c is not entry]
    config = spec.config(bench, cell["config"])
    random_config = spec.config(bench, "paper_sort_60mb")
    for key in ("system", "topology", "processors", "mesh", "n", "dtype", "guarantee",
                "rehearsal"):
        assert config[key] == random_config[key], key
    assert config["n"] == 15_728_640 and config["mesh"] is None
    traffic = spec.traffic(cell["traffic"])
    assert (traffic["kind"], traffic["keys"]) == ("balance_sort_loop", LOCAL)
    skip = ("kind", "about", "keys")
    assert {k: v for k, v in traffic.items() if k not in skip} == {
        k: v for k, v in spec.traffic("random").items() if k not in skip}


# ------------------------------------------------------------ the keys
@pytest.mark.parametrize("seed", SEEDS)
def test_local_keys_read_as_local(seed):
    from repro.core import estimate_stats

    arrays = gen.sort_arrays(1 << 18, 3, "int32", LOCAL, seed)
    assert not np.array_equal(arrays[0], arrays[1])
    for x in arrays:
        assert x.dtype == np.int32 and x.size == 1 << 18
        # A sample of 2^14 keys holds some of the n/1000 tail keys (it
        # misses all of them with odds e^-16), so it sees the whole span.
        assert estimate_stats(x, num_buckets=36, sample_size=1 << 14).label == "local"
        # The engine's 2,048-key sample misses the tail with odds e^-2;
        # then it reads the cluster alone, which is skewed all the same.
        assert estimate_stats(x, num_buckets=36).skewed


@pytest.mark.parametrize("seed", SEEDS)
def test_local_keys_repeat_per_seed(seed):
    a = gen.sort_arrays(1 << 12, 2, "int32", LOCAL, seed)
    b = gen.sort_arrays(1 << 12, 2, "int32", LOCAL, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


# ------------------------------------------------------------ the loop
class FakeEngine:
    """Sorts with numpy and reports what ``report(x)`` gives."""

    trace_count = 0
    topo = SimpleNamespace(total_procs=36)

    def __init__(self, report):
        self.report = report
        self.last_report = None

    def sort(self, x):
        self.last_report = self.report(x)
        return np.sort(x)


REPORTS = {
    "none": lambda x: None,  # a sort entry point that reports nothing
    "no-counts": lambda x: {"plan": None, "n": x.size, "overflow_retries": 0},
    "counts": lambda x: {"plan": None, "n": x.size, "overflow_retries": 0,
                         "counts": np.array([x.size // 4, 3 * x.size // 4])},
}


@pytest.mark.parametrize("report", list(REPORTS))
def test_loop_records_the_balance_counter_only_where_reported(report, monkeypatch):
    loops = spec.load_named("loops", "balance_sort_loop")
    monkeypatch.setattr(loops.sort_loop, "build_engine",
                        lambda config, devices: FakeEngine(REPORTS[report]))
    config = {"n": 64, "dtype": "int32", "processors": 36}
    traffic = {"kind": "balance_sort_loop", "keys": LOCAL, "arrays": 2}
    loop = loops.Loop(config, traffic, 3000000019, 0.02, devices=None)
    loop.setup()
    loop.window()
    counters = loop.counters
    assert counters["calls"] == loop.attempted > 0
    assert all(np.array_equal(a, np.sort(r)) for r, a in loop.answers())
    run = Run({}, config, traffic, counters, None, None, 1)
    if report == "counts":
        assert counters["max_bucket_shares"] == [0.75] * counters["calls"]
        assert spec.reader("bucket_imbalance")(run) == 27.0
    else:
        assert "max_bucket_shares" not in counters
        assert spec.reader("bucket_imbalance")(run) is None


@pytest.mark.parametrize("path, runs", [("host", False), ("sim", True)])
def test_loop_stops_at_setup_where_the_keys_plan_the_host(path, runs, monkeypatch):
    # a program that sorts these keys on the host leaves the chip idle in
    # the window: it cannot run the cell, and says so before any window
    loops = spec.load_named("loops", "balance_sort_loop")
    plan = SimpleNamespace(path=path, method="paper", reason="why")
    monkeypatch.setattr(loops.sort_loop, "build_engine", lambda config, devices: FakeEngine(
        lambda x: {"plan": plan, "n": x.size, "overflow_retries": 0}))
    config = {"n": 64, "dtype": "int32", "processors": 36}
    traffic = {"kind": "balance_sort_loop", "keys": LOCAL, "arrays": 2}
    loop = loops.Loop(config, traffic, 3000000019, 0.02, devices=None)
    if runs:
        loop.setup()
        loop.window()
        assert loop.counters["plans"] == "sim/paper"
    else:
        with pytest.raises(SystemExit) as stop:
            loop.setup()
        assert "host/paper" in str(stop.value.code) and "cannot run" in str(stop.value.code)


# ------------------------------------------------------------ the reader
@pytest.mark.parametrize("shares, want", [([1 / 36], 1.0), ([0.03, 0.04], 1.26), ([1.0], 36.0)])
def test_bucket_imbalance_is_p_times_the_mean_share(shares, want):
    run = Run({"name": CELL}, {"processors": 36}, {}, {"max_bucket_shares": shares}, None, None, 1)
    assert spec.reader("bucket_imbalance")(run) == pytest.approx(want)


def test_trace_readers_without_a_trace_read_nothing():
    run = Run({"name": CELL}, {"n": 15_728_640, "processors": 36}, {}, {"itemsize": 4}, None,
              {"hbm_bytes_per_s": 819e9}, 1)
    for name, (source, _, _) in METRICS.items():
        if source in ("device_trace", "program_span"):
            assert spec.reader(name)(run) is None, name


# ------------------------------------------------------------ rehearsal
def test_traced_rehearsal_sorts_on_the_sampled_path(repo_root, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000019",
           "--seconds", "1", "--trace", "1", "--cpu-rehearsal"]
    proc = subprocess.run(cmd, cwd=repo_root, env=env, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    (summary,) = [s for s in proc.stdout.splitlines() if s.startswith(f"chipbench: cell={CELL}")]
    assert " plans='sim/sampled' " in summary and " overflow_retries=0 " in summary
    assert set(line["metrics"]) == {"bucket_imbalance", "sort_pad_share.local"}
    assert 1.0 <= line["metrics"]["bucket_imbalance"]["value"] <= 1.5
