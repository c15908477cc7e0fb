"""The reduction from a profiler trace to device time, on a trimmed extract
of a trace recorded on a TPU v5e (``fixtures/``) and on small made-up ones."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import spec
from chipbench import trace as tr
from chipbench.drivers import SPAN_CALL
from chipbench.run import Run

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = {"hbm_bytes_per_s": 819e9}


def load(name):
    return json.loads((FIXTURES / name).read_text())


@pytest.fixture(scope="module")
def sort_trace():
    return tr.Trace(load("sort_trace.json"))


def made_up(ops, window=(0, 100), host=(), modules=(), devices=1, extra_lines=()):
    """An extract with one window span, ``ops`` as (start, end, text) on
    every device, and extra host events (start, end, name)."""
    host_events = [[window[0], window[1] - window[0], tr.WINDOW_SPAN]]
    host_events += [[s, e - s, n] for s, e, n in host]
    planes = [{"name": "/host:CPU", "lines": [{"name": "main", "events": host_events}]}]
    for d in range(devices):
        lines = [{"name": tr.OPS_LINE, "events": [[s, e - s, n] for s, e, n in ops]},
                 {"name": tr.MODULES_LINE, "events": [[s, e - s, n] for s, e, n in modules]}]
        lines += [{"name": name, "events": [[s, e - s, n] for s, e, n in evs]}
                  for name, evs in extra_lines]
        planes.append({"name": f"/device:TPU:{d}", "lines": lines})
    return tr.Trace({"planes": planes})


def brute_busy_ns(trace, device):
    """Busy time by a sweep over sorted edges, a second way to the union."""
    edges = []
    for s, e, _ in trace.ops.get(device, ()):
        s, e = max(s, trace.t0), min(e, trace.t1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    depth, last, busy = 0, None, 0.0
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


# ------------------------------------------------------------- interval maths
def test_union_clip_total():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.clip([(0, 10), (20, 30), (40, 50)], 5, 45) == [(5, 10), (20, 30), (40, 45)]
    assert tr.total([(0, 3), (5, 8)]) == 6.0


def test_busy_and_idle_on_a_made_up_trace():
    t = made_up([(10, 20, "a"), (15, 30, "b"), (50, 60, "c"), (90, 120, "d")])
    assert t.busy(0) == [[10, 30], [50, 60], [90, 100]]
    assert t.busy_s(0) == pytest.approx(40e-9)
    assert t.idle_share(0) == pytest.approx(0.6)


def test_device_lines_other_than_ops_do_not_count_as_busy():
    """Async copies and host-side transfers are not device compute."""
    ops = [(10, 20, "%fusion = s32[8]{0} fusion(s32[8]{0} %x)")]
    copies = [("Async XLA Ops", [(0, 100, "%copy-start = (s32[8]) copy-start(s32[8] %x)")])]
    host = [(0, 100, "tpu::System::TransferToDevice"), (20, 90, "D2H Dispatch")]
    t = made_up(ops, host=host, extra_lines=copies)
    assert t.busy_s(0) == pytest.approx(10e-9)


def test_idle_is_named_by_the_most_specific_host_event():
    ops = [(0, 400_000, "%a = s32[1]{0} fusion()"), (800_000, 1_000_000, "%b = s32[1]{0} fusion()")]
    host = [(0, 1_000_000, "loop"), (400_000, 600_000, "pad"), (600_000, 700_000, "copy")]
    t = made_up(ops, window=(0, 1_000_000), host=host)
    got = dict(t.idle_by_host(0))
    assert got["pad"] == pytest.approx(200e-6)
    assert got["copy"] == pytest.approx(100e-6)
    assert got["loop"] == pytest.approx(100e-6)
    assert sum(got.values()) == pytest.approx(1e-3 - t.busy_s(0))


# ------------------------------------------------------------- collectives
COLLECTIVE_TEXTS = [
    "%all-to-all.1 = s32[4,983040]{1,0} all-to-all(s32[4,983040]{1,0} %fusion.2), "
    "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}",
    "%all-gather-start = (s32[4]{0}, s32[16]{0}) all-gather-start(s32[4]{0} %x), dimensions={0}",
    "%all-reduce.3 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %m), to_apply=%max",
    "%collective-permute-done = s32[8]{0} collective-permute-done(s32[8]{0} %cp)",
]
COMPUTE_TEXTS = [
    "%fusion.3 = s32[16777217]{0:T(1024)S(1)} fusion(s32[33554592]{0:T(1024)} %gte.3), "
    "kind=kCustom, calls=%fused_computation.33",
    "%sort.6 = (s32[32,4096]{1,0:T(8,128)}, s32[32,4096]{1,0:T(8,128)}) sort(s32[32,4096] %c, "
    "s32[32,4096] %iota), dimensions={1}, is_stable=true, to_apply=%region_0.1.clone",
    "%copy-start = (s32[36]{0:T(128)S(1)}, s32[36]{0:T(128)}, u32[]{:S(2)}) copy-start(s32[36] %a.1)",
]


@pytest.mark.parametrize("text", COLLECTIVE_TEXTS)
def test_collectives_are_recognised(text):
    assert tr.is_collective(text)


@pytest.mark.parametrize("text", COMPUTE_TEXTS)
def test_compute_is_not_a_collective(text):
    assert not tr.is_collective(text)


def test_collective_and_compute_split_over_four_devices(mesh_root):
    ops = [(0, 30, COMPUTE_TEXTS[0]), (30, 40, COLLECTIVE_TEXTS[0]), (35, 45, COLLECTIVE_TEXTS[2]),
           (60, 70, COMPUTE_TEXTS[1])]
    t = made_up(ops, devices=4)
    run = Run({}, {}, {}, {}, t, PEAKS, 4)
    for d in range(4):
        assert t.op_time_s(d, tr.is_collective) == pytest.approx(15e-9)
        assert t.busy_s(d) == pytest.approx(55e-9)
    share = spec.reader("dist_collective_share", mesh_root)(run)
    assert share == pytest.approx(100 * 15 / 55)


@pytest.mark.parametrize("text,want", [
    (COMPUTE_TEXTS[0], "fusion s32[16777217] %fusion.3"),
    (COMPUTE_TEXTS[1], "sort s32[32,4096] %sort.6"),
    (COLLECTIVE_TEXTS[0], "all-to-all s32[4,983040] %all-to-all.1"),
])
def test_op_label(text, want):
    assert tr.op_label(text) == want


# ------------------------------------------------- recorded: the 60 MB sort
def test_recorded_sort_busy_union(sort_trace):
    assert sort_trace.busy_s(0) * 1e9 == pytest.approx(brute_busy_ns(sort_trace, 0), rel=1e-12)
    assert 0.0 < sort_trace.idle_share(0) < 1.0
    # the module runs cover the op events: busy time sits inside them
    calls = sort_trace.spans(SPAN_CALL)
    runs = sort_trace.module_runs(0, inside=calls)
    assert sum(e - s for s, e, _ in runs) * 1e-9 >= sort_trace.busy_s(0) * 0.999


def test_recorded_sort_roofline_and_idle(sort_trace):
    calls = [c for c in sort_trace.spans(SPAN_CALL) if sort_trace.t0 <= c[0] and c[1] <= sort_trace.t1]
    assert len(calls) == 2
    run = Run({}, {"n": 15728640}, {}, {"itemsize": 4}, sort_trace, PEAKS, 1)
    share = spec.reader("sim_sort_roofline")(run)
    # 125.8 MB at 819 GB/s is 0.154 ms, against ~0.97 s of device time a call
    assert share == pytest.approx(0.0158, rel=0.02)
    idle = spec.reader("device_idle_share.sort")(run)
    assert idle == pytest.approx(100 * sort_trace.idle_share(0))
    top = sort_trace.top_ops(0)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = sort_trace.idle_by_host(0)
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert sum(s for _, s in gaps) <= sort_trace.window_s - sort_trace.busy_s(0) + 1e-4


def test_no_trace_no_device_metric(mesh_root):
    run = Run({}, {"n": 10}, {}, {"itemsize": 4}, None, None, 1)
    for name in ("sim_sort_roofline", "device_idle_share.sort", "dist_collective_share"):
        assert spec.reader(name, mesh_root)(run) is None


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.Trace({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_brute_force_agrees_on_random_intervals():
    rng = np.random.default_rng(0)
    starts = rng.integers(0, 10_000, 500)
    ops = [(int(s), int(s + d), "x") for s, d in zip(starts, rng.integers(1, 200, 500))]
    t = made_up(ops, window=(1_000, 9_000))
    assert t.busy_s(0) * 1e9 == pytest.approx(brute_busy_ns(t, 0))
