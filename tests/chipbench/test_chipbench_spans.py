"""The per-call readings of the engine's spans (``chipbench/spans.py``) and
the five readers on them, on small made-up extracts and on a trimmed
extract of a traced run of ``sort_60mb_random`` on a TPU v5e
(``fixtures/sort_trace_spans.json``)."""

import json
from pathlib import Path

import pytest

from chipbench import spans, spec
from chipbench import trace as tr
from chipbench.drivers import SPAN_CALL
from chipbench.run import Run

FIXTURES = Path(__file__).resolve().parent / "fixtures"
READERS = ["sort_pad_ms", "sort_h2d_ms", "sort_d2h_ms", "sort_dispatch_idle_ms",
           "sim_sort_device_ms"]
STAGES = ["sort_engine.plan", spans.PAD, spans.H2D, spans.EXECUTE, spans.D2H]


def made_up(host, ops=(), modules=(), window=(0, 10_000_000)):
    """An extract with a window span, host spans (start, end, name) on one
    thread, and ops and module runs (start, end, name) on one device."""
    def events(evs):
        return [[s, e - s, n] for s, e, n in evs]

    host_events = [[window[0], window[1] - window[0], tr.WINDOW_SPAN]] + events(host)
    return tr.Trace({"planes": [
        {"name": tr.HOST_PLANE, "lines": [{"name": "python3", "events": host_events}]},
        {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": events(ops)},
                                            {"name": tr.MODULES_LINE, "events": events(modules)}]},
    ]})


def call(t, *, pad=1_000, h2d=500, execute=(4_000,), d2h=2_000, gap=100):
    """Host spans of one sim-path call starting at ``t``: each stage takes
    its given ns, ``gap`` ns apart; several ``execute`` are retries."""
    out, cur = [], t + gap
    stages = [(spans.PAD, pad), (spans.H2D, h2d), *((spans.EXECUTE, d) for d in execute),
              (spans.D2H, d2h)]
    for name, dur in stages:
        out.append((cur, cur + dur, name))
        cur += dur + gap
    return [(t, cur, spans.SORT)] + out


def run_of(trace):
    return Run({}, {"n": 15728640}, {}, {"itemsize": 4}, trace, {"hbm_bytes_per_s": 819e9}, 1)


def read(name, trace):
    return spec.reader(name)(run_of(trace))


def test_stage_time_is_summed_over_whole_calls_and_divided_by_their_count():
    host = call(1_000) + call(20_000, pad=3_000) + call(9_995_000)  # the last ends after the window
    t = made_up(host)
    assert len(spans.calls(t)) == 2
    assert read("sort_pad_ms", t) == pytest.approx((1_000 + 3_000) / 2 * 1e-6)
    assert read("sort_h2d_ms", t) == pytest.approx(500e-6)
    assert read("sort_d2h_ms", t) == pytest.approx(2_000e-6)


def test_a_call_that_starts_before_the_window_is_left_out():
    t = made_up(call(0) + call(50_000, d2h=8_000), window=(500, 10_000_000))
    assert [s for s, _ in spans.calls(t)] == [50_000]
    assert read("sort_d2h_ms", t) == pytest.approx(8_000e-6)


def test_retries_sum_in_the_execute_readings():
    host = call(1_000, execute=(4_000, 6_000))
    (s1, _), (s2, _) = [(s, e) for s, e, n in host if n == spans.EXECUTE]
    ops = [(s1 + 1_000, s1 + 3_000, "%fusion"), (s2, s2 + 5_000, "%fusion.1")]
    t = made_up(host, ops=ops)
    assert spans.stage_ms(t, spans.EXECUTE) == pytest.approx(10_000e-6)
    # idle: 4,000 − 2,000 busy in the first, 6,000 − 5,000 in the second
    assert read("sort_dispatch_idle_ms", t) == pytest.approx(3_000e-6)


def test_idle_counts_only_time_inside_execute_spans():
    host = call(1_000)
    ex = [(s, e) for s, e, n in host if n == spans.EXECUTE][0]
    ops = [(0, ex[0] + 1_000, "%before_and_into"), (ex[1] - 500, ex[1] + 9_000, "%into_and_after")]
    t = made_up(host, ops=ops)
    assert read("sort_dispatch_idle_ms", t) == pytest.approx((4_000 - 1_000 - 500) * 1e-6)


def test_device_time_reads_only_the_sim_sort_module_inside_calls():
    host = call(1_000) + call(100_000)
    ex = [(s, e) for s, e, n in host if n == spans.EXECUTE]
    modules = [(ex[0][0] + 10, ex[0][0] + 3_010, "jit_sim_sort(1234)"),
               (ex[0][0] + 3_100, ex[0][0] + 3_200, "jit__reduce_sum(99)"),
               (ex[1][0] + 10, ex[1][0] + 3_510, "jit_sim_sort(1234)"),
               (5_000_000, 5_400_000, "jit_sim_sort(1234)")]  # outside any call
    t = made_up(host, modules=modules)
    assert read("sim_sort_device_ms", t) == pytest.approx((3_000 + 3_500) / 2 * 1e-6)


def test_a_program_without_the_spans_reads_nothing():
    """The parent's trace: the benchmark's spans, ``jit_traced`` modules."""
    t = made_up([(1_000, 9_000, SPAN_CALL)], ops=[(2_000, 8_000, "%fusion")],
                modules=[(2_000, 8_000, "jit_traced(1)")])
    for name in READERS:
        assert read(name, t) is None, name


def test_a_call_without_a_stage_reads_nothing_for_that_stage():
    """The host path has no pad, copies or executable."""
    t = made_up([(1_000, 9_000, spans.SORT), (1_100, 1_200, "sort_engine.plan"),
                 (1_300, 8_000, "sort_engine.host_sort")])
    for name in READERS:
        assert read(name, t) is None, name


def test_no_trace_no_reading():
    for name in READERS:
        assert spec.reader(name)(run_of(None)) is None, name


def test_the_readers_use_the_engines_names():
    from repro.core import engine

    assert (spans.SORT, spans.PAD, spans.H2D, spans.EXECUTE, spans.D2H) == (
        engine.SPAN_SORT, engine.SPAN_PAD, engine.SPAN_H2D, engine.SPAN_EXECUTE, engine.SPAN_D2H)
    assert STAGES[0] == engine.SPAN_PLAN


def test_the_device_reader_names_the_engines_sim_executable():
    import jax
    import numpy as np

    from repro.core import SortEngine

    fn = SortEngine()._get_sim_fn(1024, 64, "paper", np.int32, False)
    text = fn.lower(jax.ShapeDtypeStruct((1024,), np.int32), 1000).as_text()
    module = spec.load_named("metrics", "sim_sort_device_ms").MODULE
    assert text.splitlines()[0].startswith(f"module @{module} ")


# --------------------------- recorded: sort_60mb_random on a TPU v5e, traced
@pytest.fixture(scope="module")
def recorded():
    return tr.Trace(json.loads((FIXTURES / "sort_trace_spans.json").read_text()))


def test_recorded_run_reads_what_its_result_line_printed(recorded):
    """Seed 3513000301, a 2-s window of two calls; the numbers are the
    run's own result line."""
    got = {name: read(name, recorded) for name in READERS}
    assert got == pytest.approx({"sort_pad_ms": 61.186193, "sort_h2d_ms": 3.4259505,
                                 "sort_d2h_ms": 71.4976525, "sort_dispatch_idle_ms": 21.1672445,
                                 "sim_sort_device_ms": 969.728247}, rel=1e-6)


def test_recorded_stage_spans_nest_and_cover_the_call(recorded):
    benchmark_calls = [c for c in recorded.spans(SPAN_CALL)
                       if recorded.t0 <= c[0] and c[1] <= recorded.t1]
    calls = spans.calls(recorded)
    assert len(calls) == len(benchmark_calls) == 2
    assert tr.total(calls) >= 0.99 * tr.total(benchmark_calls)
    stages = [iv for name in STAGES for iv in recorded.spans(name)]
    assert all(any(s <= a and b <= e for s, e in calls) for a, b in stages)
    assert tr.total(stages) >= 0.95 * tr.total(calls)
    assert len(recorded.spans(spans.EXECUTE)) == 2  # no overflow retry


def test_recorded_modules_carry_the_engines_names(recorded):
    names = {n.split("(", 1)[0] for _, _, n in recorded.module_runs(0)}
    assert names == {"jit_sim_sort", "jit__reduce_sum"}
    # per call, the roofline's device time (every module run inside the
    # benchmark's span) is the sim sort plus the microseconds of the counts
    # reduction
    share = spec.reader("sim_sort_roofline")(run_of(recorded)) / 100
    roofline_s = 2 * 15728640 * 4 / 819e9 / share
    assert read("sim_sort_device_ms", recorded) * 1e-3 == pytest.approx(roofline_s, rel=1e-5)
