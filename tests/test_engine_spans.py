"""``SortEngine.sort``'s profiler spans and the names of the executables it
builds (DESIGN.md §4), read back from a ``jax.profiler`` trace taken on
the CPU and reduced by the benchmark's own ``chipbench.trace``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import trace as tr  # noqa: E402
from repro.core import SortEngine, engine  # noqa: E402
from repro.core.engine import SortPlan  # noqa: E402

N = 5000
SIM_STAGES = [engine.SPAN_PLAN, engine.SPAN_PAD, engine.SPAN_H2D, engine.SPAN_EXECUTE,
              engine.SPAN_D2H]


def keys(n=N, seed=0):
    return np.random.default_rng(seed).integers(0, 2**31 - 1, n, dtype=np.int32)


def profiled(directory, fn):
    """Run ``fn`` under the profiler inside a window span; return its
    result and the reduced trace."""
    jax.profiler.start_trace(str(directory))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, tr.Trace.from_file(next(Path(directory).rglob("*.xplane.pb")))


def engine_events(trace):
    """``(start, end, name, thread)`` of the engine's spans, in order."""
    return sorted(h for h in trace.host if h[2].startswith("sort_engine."))


def stages_of_one_call(trace):
    """The names of the stage spans, in order, after checking that there is
    one ``sort_engine.sort`` span and every stage nests in it, on its
    thread."""
    events = engine_events(trace)
    calls = [h for h in events if h[2] == engine.SPAN_SORT]
    assert len(calls) == 1, events
    s0, e0, _, thread = calls[0]
    stages = [h for h in events if h[2] != engine.SPAN_SORT]
    for s, e, name, line in stages:
        assert s0 <= s <= e <= e0, name
        assert line == thread, name
    return [name for _, _, name, _ in stages]


def test_sim_path_spans_nest_in_order(tmp_path):
    eng = SortEngine()
    x = keys()
    out, trace = profiled(tmp_path, lambda: eng.sort(x))
    assert np.array_equal(out, np.sort(x))
    assert eng.last_report["plan"].path == "sim"
    assert stages_of_one_call(trace) == SIM_STAGES


@pytest.mark.parametrize("warm", [False, True], ids=["first", "reused"])
def test_sim_pad_is_one_plain_span_with_a_first_or_reused_buffer(tmp_path, warm):
    eng = SortEngine()
    if warm:
        eng.sort(keys(N - 7, seed=1))  # leaves the bucket's buffer in the pool
    x = keys()
    out, trace = profiled(tmp_path, lambda: eng.sort(x))
    assert np.array_equal(out, np.sort(x))
    assert eng.last_report["pad_reused"] is warm
    assert stages_of_one_call(trace) == SIM_STAGES  # nested in the call, on its thread
    pads = [h[2] for h in trace.host if h[2].startswith(engine.SPAN_PAD)]
    assert pads == ["sort_engine.pad"]


def test_host_path_spans(tmp_path):
    eng = SortEngine(host_threshold=1000)
    x = keys()
    out, trace = profiled(tmp_path, lambda: eng.sort(x))
    assert np.array_equal(out, np.sort(x))
    assert eng.last_report["plan"].path == "host"
    assert stages_of_one_call(trace) == [engine.SPAN_PLAN, engine.SPAN_HOST_SORT]


def test_trivial_sort_is_one_span(tmp_path):
    out, trace = profiled(tmp_path, lambda: SortEngine().sort(np.array([7], np.int32)))
    assert out.tolist() == [7]
    assert stages_of_one_call(trace) == []


def test_an_overflow_retry_is_a_second_execute_span(tmp_path):
    eng = SortEngine()
    x = keys()
    # 5,000 keys over 36 buckets average 139 a bucket: 128 slots overflow
    plan = SortPlan("sim", "paper", 128, None, "capacity forced too small")
    out, trace = profiled(tmp_path, lambda: eng.sort(x, plan=plan))
    assert np.array_equal(out, np.sort(x))
    retries = eng.last_report["overflow_retries"]
    assert retries >= 1
    stages = stages_of_one_call(trace)
    assert stages.count(engine.SPAN_EXECUTE) == retries + 1
    assert stages[:3] == SIM_STAGES[:3] and stages[-1] == engine.SPAN_D2H


@pytest.mark.parametrize("host_threshold", [engine.HOST_THRESHOLD, 1000])
def test_sort_outside_a_profiler_is_exact(host_threshold):
    eng = SortEngine(host_threshold=host_threshold)
    for seed in range(3):
        x = keys(N + seed * 777, seed)
        assert np.array_equal(eng.sort(x), np.sort(x))


def expected_module(key):
    if key[0] not in ("sim", "batch"):
        return {"pairs": "jit_pairs_sort", "topk": "jit_sim_topk", "dist": "jit_dist_sort"}[key[0]]
    if key[3] == "bitonic":
        return "jit_row_sort"
    return "jit_sim_sort"


def lowering_args(key):
    """Argument shapes for a cached executable, from its cache key."""
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if key[0] == "sim":
        return jax.ShapeDtypeStruct((key[1],), key[4]), scalar
    if key[0] == "batch":
        return jax.ShapeDtypeStruct((8, key[1]), key[4]), jax.ShapeDtypeStruct((8,), jnp.int32)
    if key[0] == "pairs":
        return (jax.ShapeDtypeStruct((key[1],), key[2]), jax.ShapeDtypeStruct((key[1],), key[3]),
                scalar)
    if key[0] == "topk":
        return jax.ShapeDtypeStruct((key[1],), key[4]), scalar
    raise KeyError(key)


def module_name(fn, args):
    first = fn.lower(*args).as_text().splitlines()[0]
    assert first.startswith("module @"), first
    return first.split()[1][1:]


def test_every_cached_executable_has_its_stable_name():
    eng = SortEngine()
    x = keys()
    eng.sort(x)  # sim paper
    eng.sort_segments(x[:300], [100, 200])  # bitonic rows
    eng.sort_segments(keys(2 * 9000), [9000, 9000])  # bucket rows, vmapped
    eng.sort_pairs(x, np.arange(N, dtype=np.int32))
    eng.top_k(x, N // 2)
    kinds = {(k[0], expected_module(k)) for k in eng._fn_cache}
    assert kinds == {("sim", "jit_sim_sort"), ("batch", "jit_row_sort"),
                     ("batch", "jit_sim_sort"), ("pairs", "jit_pairs_sort"),
                     ("topk", "jit_sim_topk")}
    for key, fn in eng._fn_cache.items():
        assert module_name(fn, lowering_args(key)) == expected_module(key), key


DIST_SCRIPT = r"""
import json, os, sys, tempfile
from pathlib import Path
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from chipbench import trace as tr
from repro import compat
from repro.core import SortEngine

mesh = compat.make_mesh((4,), ("data",))
eng = SortEngine(mesh=mesh)
x = np.random.default_rng(1).integers(0, 2**31 - 1, 4099, dtype=np.int32)
d = tempfile.mkdtemp(dir=sys.argv[2])
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
    out = eng.sort(x)
jax.profiler.stop_trace()
trace = tr.Trace.from_file(next(Path(d).rglob("*.xplane.pb")))
spans = sorted(h for h in trace.host if h[2].startswith("sort_engine."))
modules = []
for key, fn in eng._fn_cache.items():
    arg = jax.device_put(np.zeros(key[1], key[2]), NamedSharding(mesh, PartitionSpec("data")))
    modules.append(fn.lower(arg).as_text().splitlines()[0].split()[1][1:])
print(json.dumps({"exact": bool(np.array_equal(out, np.sort(x))),
                  "path": eng.last_report["plan"].path,
                  "spans": [[s, e, n, line] for s, e, n, line in spans],
                  "modules": modules}))
"""


def test_dist_path_spans_on_four_devices(tmp_path):
    proc = subprocess.run([sys.executable, "-c", DIST_SCRIPT, str(ROOT), str(tmp_path)],
                          env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["exact"] and got["path"] == "dist"
    assert got["modules"] == ["jit_dist_sort"]
    spans = got["spans"]
    (s0, e0, _, thread), = [h for h in spans if h[2] == engine.SPAN_SORT]
    stages = [h for h in spans if h[2] != engine.SPAN_SORT]
    assert all(s0 <= s <= e <= e0 and line == thread for s, e, _, line in stages)
    assert [h[2] for h in stages] == [engine.SPAN_PLAN, engine.SPAN_PAD, engine.SPAN_H2D,
                                      engine.SPAN_EXECUTE, engine.SPAN_D2H, engine.SPAN_UNPACK]
