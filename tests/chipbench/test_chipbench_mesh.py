"""The four-chip cell ``dist_sort_60mb_mesh4``: its entries in the real
``BENCHMARK.json``, a CPU rehearsal on four virtual devices, the loop's
dist-path counters, and its readers on a made-up four-device trace."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import spec
from chipbench import trace as tr
from chipbench.drivers import SPAN_CALL
from chipbench.run import Run

CELL = "dist_sort_60mb_mesh4"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
DIST_LAYER = "dist sort executable (core/dist_sort.py)"
PLANNING = "engine planning and capacity (core/engine.py)"
STAGING = "engine host staging (core/engine.py)"
# name: (source, layer), in BENCHMARK.json's order
METRICS = {
    "dist_collective_share": ("device_trace", DIST_LAYER),
    "dist_sort_device_ms": ("device_trace", DIST_LAYER),
    "dist_sort_roofline": ("device_trace", DIST_LAYER),
    "dist_slot_pad_share": ("program_counter", PLANNING),
    "dist_unpack_ms": ("program_span", STAGING),
    "device_idle_share.dist": ("device_trace", "device"),
    "sort_h2d_ms.dist": ("program_span", STAGING),
    "sort_d2h_ms.dist": ("program_span", STAGING),
    "sort_dispatch_idle_ms.dist": ("device_trace", "device"),
}
SIM_METRICS = ["sort_pad_share", "device_idle_share.sort", "sim_sort_roofline", "sort_pad_ms",
               "sort_h2d_ms", "sort_d2h_ms", "sort_dispatch_idle_ms", "sim_sort_device_ms"]


# ------------------------------------------------------------ the entries
def test_the_cell_reports_exactly_its_metrics():
    bench = spec.load_benchmark()
    e2e, layer = spec.metrics_for(bench, CELL)
    assert [m["name"] for m in e2e] == ["sort_keys_per_s", "setup_s"]
    assert [m["name"] for m in layer] == list(METRICS)
    for m in layer:
        assert (m["source"], m["layer"]) == METRICS[m["name"]], m["name"]
        assert m["moves"] == "sort_keys_per_s" and m["workloads"] == [CELL]
        assert spec.reader(m["name"]) is not None
    _, sim_layer = spec.metrics_for(bench, "sort_60mb_random")
    assert [m["name"] for m in sim_layer] == SIM_METRICS


def test_the_cell_and_its_configuration():
    bench = spec.load_benchmark()
    cell = spec.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("paper_sort_60mb_mesh4",
                                                                "random_mesh", 4)
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"].startswith("https://arxiv.org/abs/2109.05176 Sec. 6: ")
    assert (entry["file"], entry["reduced"]) == ("chipbench/configs/paper_sort_60mb_mesh4.json", [])
    # Two deployments of one paper name the parts that define them.
    assert entry["source"] not in [c["source"] for c in bench["configs"] if c is not entry]
    config = spec.config(bench, cell["config"])
    assert config == json.loads((FIXTURES / "paper_sort_60mb_mesh4.json").read_text())
    assert config["n"] == 15_728_640 and config["mesh"] == {"shape": [4], "axes": ["data"]}
    traffic = spec.traffic(cell["traffic"])
    assert traffic["kind"] == "mesh_sort_loop"
    assert {k: v for k, v in traffic.items() if k not in ("kind", "about")} == {
        k: v for k, v in spec.traffic("random").items() if k not in ("kind", "about")}


# ------------------------------------------------------------ rehearsal
def run_cell(root, *, chips, cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000019",
           "--seconds", "1", "--trace", "0", "--cpu-rehearsal"]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True, timeout=240)


def test_rehearsal_on_four_devices_sorts_on_the_dist_path(repo_root, tmp_path):
    proc = run_cell(repo_root, chips=4, cache_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    (summary,) = [s for s in proc.stdout.splitlines() if s.startswith(f"chipbench: cell={CELL}")]
    assert " plans='dist/paper' " in summary and " overflow_retries=0 " in summary
    assert " compiles_in_window=0 " in summary


def test_rehearsal_with_one_device_does_not_run(repo_root, tmp_path):
    proc = run_cell(repo_root, chips=1, cache_dir=tmp_path)
    assert proc.returncode == 3 and proc.stdout.strip() == ""


# ------------------------------------------------------------ the loop
class FakeEngine:
    """Sorts with numpy and reports what ``report(n)`` gives."""

    trace_count = 0
    topo = SimpleNamespace(total_procs=36)

    def __init__(self, report):
        self.report = report
        self.last_report = None

    def sort(self, x):
        self.last_report = self.report(x.size)
        return np.sort(x)


REPORTS = {
    "none": lambda n: None,  # a sort entry point that reports nothing
    "parent": lambda n: {"plan": None, "n": n, "overflow_retries": 0, "counts_sum": n},
    "counters": lambda n: {"plan": None, "n": n, "overflow_retries": 0, "counts_sum": n,
                           "dist_capacity": n // 8, "shard_counts": [n // 4] * 4},
}


@pytest.mark.parametrize("report", list(REPORTS))
def test_loop_records_the_dist_counters_only_where_reported(report, monkeypatch):
    loops = spec.load_named("loops", "mesh_sort_loop")
    monkeypatch.setattr(loops.sort_loop, "build_engine",
                        lambda config, devices: FakeEngine(REPORTS[report]))
    config = {"n": 64, "dtype": "int32"}
    traffic = {"kind": "mesh_sort_loop", "keys": {"distribution": "random"}, "arrays": 2}
    loop = loops.Loop(config, traffic, 3000000019, 0.02, devices=None)
    loop.setup()
    loop.window()
    counters = loop.counters
    assert counters["calls"] == loop.attempted > 0
    assert all(np.array_equal(a, np.sort(r)) for r, a in loop.answers())
    run = Run({}, config, traffic, counters, None, None, 4)
    if report == "counters":
        # 64 keys in 4 x 4 rows of 8 slots: half the slots hold no key
        assert counters["slot_pad_shares"] == [0.5] * counters["calls"]
        assert counters["shard_counts"] == [[16] * 4] * counters["calls"]
        assert spec.reader("dist_slot_pad_share")(run) == 50.0
    else:
        assert "slot_pad_shares" not in counters and "shard_counts" not in counters
        assert spec.reader("dist_slot_pad_share")(run) is None


# ------------------------------------------------------------ the readers
MS = 1_000_000  # ns
N = 15_728_640
COLLECTIVE_MS = [1, 2, 3, 4]  # the all_to_all's time on each chip, per call
CALLS = [10 * MS, 90 * MS]


def four_device_trace():
    """A 200-ms window of two whole calls and one that outlasts it.  Each
    call's execute span holds one ``jit_dist_sort`` run of 40 ms on every
    chip: a 30-ms sort, the all_to_all, a 6-ms sort of what came in."""
    host = [[0, 200 * MS, tr.WINDOW_SPAN]]
    devices = {d: {"ops": [], "modules": []} for d in range(4)}
    for t in CALLS + [180 * MS]:
        host += [[t, 60 * MS, SPAN_CALL], [t + MS // 10, 59_800_000, "sort_engine.sort"],
                 [t + MS, 4 * MS, "sort_engine.h2d"], [t + 5 * MS, 45 * MS, "sort_engine.execute"],
                 [t + 50 * MS, 6 * MS, "sort_engine.d2h"],
                 [t + 56 * MS, 3 * MS, "sort_engine.unpack"]]
        for d, c in enumerate(COLLECTIVE_MS):
            devices[d]["modules"].append([t + 6 * MS, 40 * MS, "jit_dist_sort(77)"])
            devices[d]["ops"] += [
                [t + 6 * MS, 30 * MS, "%sort.3 = s32[3932160]{0} sort(s32[3932160]{0} %p)"],
                [t + 36 * MS, c * MS,
                 "%all_to_all.10 = s32[4,1,1966080]{2,1,0} all-to-all(s32[4,1,1966080]{2,1,0} %b)"],
                [t + 40 * MS, 6 * MS, "%sort.5 = s32[7864320]{0} sort(s32[7864320]{0} %r)"]]
    devices[0]["modules"].append([170 * MS, 5 * MS, "jit_dist_sort(77)"])  # inside no call
    planes = [{"name": tr.HOST_PLANE, "lines": [{"name": "python3", "events": host}]}]
    planes += [{"name": f"/device:TPU:{d}",
                "lines": [{"name": tr.OPS_LINE, "events": v["ops"]},
                          {"name": tr.MODULES_LINE, "events": v["modules"]}]}
               for d, v in devices.items()]
    return tr.Trace({"planes": planes})


def run_of(trace, counters=None):
    return Run({"name": CELL}, {"n": N}, {}, {"itemsize": 4, **(counters or {})}, trace,
               {"hbm_bytes_per_s": 819e9}, 4)


def test_readers_on_a_four_device_trace():
    trace = four_device_trace()
    got = {name: spec.reader(name)(run_of(trace)) for name in METRICS}
    # busy per call on chip d: 30 + c_d + 6 ms; the window ends at 200 ms,
    # so of the third call's first sort (186 to 216 ms) 14 ms count
    busy = [2 * (36 + c) + 14 for c in COLLECTIVE_MS]
    collective = [2 * c for c in COLLECTIVE_MS]  # the third's starts after 200 ms
    assert got == pytest.approx({
        "dist_collective_share": 100 * np.mean([c / b for c, b in zip(collective, busy)]),
        "dist_sort_device_ms": 40.0,
        # 2 x 15,728,640 x 4 bytes at 4 x 819 GB/s is 38.409 us, over 40 ms
        "dist_sort_roofline": 0.0960234432,
        "dist_slot_pad_share": None,  # no counters in this run
        "dist_unpack_ms": 3.0,
        "device_idle_share.dist": 100 * np.mean([1 - b / 200 for b in busy]),
        "sort_h2d_ms.dist": 4.0,
        "sort_d2h_ms.dist": 6.0,
        "sort_dispatch_idle_ms.dist": 45.0 - 37.0,  # chip 0 busy 30 + 1 + 6 of it
    }, rel=1e-6)


def test_readers_without_a_trace_read_nothing():
    for name, (source, _) in METRICS.items():
        if source != "program_counter":
            assert spec.reader(name)(run_of(None)) is None, name
    assert spec.reader("dist_slot_pad_share")(run_of(None, {"slot_pad_shares": [0.5, 0.25]})) \
        == pytest.approx(37.5)


def test_the_readers_use_the_engines_names():
    from repro.core import engine

    assert spec.load_named("metrics", "dist_unpack_ms").UNPACK == engine.SPAN_UNPACK
    for name in ("dist_sort_device_ms", "dist_sort_roofline"):
        assert spec.load_named("metrics", name).MODULE == "jit_dist_sort"


def test_the_collective_reader_is_the_fixtures():
    mine = (spec.ROOT / "chipbench" / "metrics" / "dist_collective_share.py").read_text()
    assert mine == (FIXTURES / "dist_collective_share.py").read_text()
