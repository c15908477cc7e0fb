"""Run one benchmark cell once and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name (``chipbench.spec``),
builds the system under test from ``src/``, makes its inputs from
``--seed``, warms every shape the traffic uses, measures for ``--seconds``,
then judges every answer of the window against the plain reference
(``chipbench.reference``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``, each number
compared beside its limit.  The same checks close standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics and
``setup_s``; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  ``--cpu-rehearsal`` runs the cell at tiny sizes on
whatever JAX has, for the benchmark's own tests; its line names that
device, and it reports no device-trace metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import reference, roofline, spec  # noqa: E402
from chipbench import trace as tracing  # noqa: E402

NO_CHIP = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts executables JAX compiles or loads from its persistent cache."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration_s: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


class GcWatch:
    """Times the interpreter's full collections while it is on."""

    def __init__(self):
        self.pauses: list = []
        self._t = None

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Run:
    """What a per-layer reader reads: the driver's counters, the trace
    (``None`` off the chip), the device's peaks and the cell."""

    def __init__(self, cell, config, traffic, counters, trace, peaks, chips):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.counters, self.trace, self.peaks, self.chips = counters, trace, peaks, chips


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on whatever JAX has; for the benchmark's tests")
    return ap.parse_args(argv)


def devices_or_none(chips: int, rehearsal: bool):
    """The devices to run on, or ``None`` (with a reason on stderr) when
    this machine cannot run the cell."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearsal:
        print(f"chipbench: no TPU (JAX found {devices[0].platform!r}); refusing to run",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX has {len(devices)}",
              file=sys.stderr)
        return None
    return devices


def execute(args, devices, *, root: Path = ROOT, compiles: CompileCounter | None = None,
            t_start: float | None = None) -> dict:
    """Run the cell on ``devices``; return the result line as a dict."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    compiles = compiles or CompileCounter()
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], root)
    if args.cpu_rehearsal:
        config, traffic = spec.rehearsal(config), spec.rehearsal(traffic)
    e2e, per_layer = spec.metrics_for(bench, cell["name"])
    on_chip = devices[0].platform == "tpu"
    used = devices[: cell["chips"]]

    loop = spec.loop(traffic["kind"], root)
    driver = loop(config, traffic, args.seed, args.seconds, devices, root=root)
    driver.setup()
    # What set-up made lives as long as the run: keep the collector off it.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1  # Python frames name the host's share of idle time
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles_before = compiles.count
    with GcWatch() as gc_watch, jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        driver.window()
    compiles_in_window = compiles.count - compiles_before
    if args.trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    memory_peak = max((p for p in peaks_in_use if p is not None), default=None)
    end_to_end = driver.end_to_end()
    driver.close()

    # The reference runs once the window has closed and the system is freed.
    bad = checked = 0
    wants: dict = {}  # one reference per request array, however often it was sent
    for request, answer in driver.answers():
        checked += 1
        want = wants.get(id(request))
        if want is None:
            want = wants[id(request)] = reference.reference_sort(request)
        if not reference.same_answer(answer, want):
            bad += 1
    del wants

    counters = driver.counters
    scalars = " ".join(f"{k}={v!r}" for k, v in counters.items() if isinstance(v, (int, float, str)))
    print(f"chipbench: cell={cell['name']} seed={args.seed} setup_s={setup_s!r} "
          f"compiles_in_window={compiles_in_window} checked={checked} "
          f"full_gc_in_window={len(gc_watch.pauses)} "
          f"full_gc_max_ms={max(gc_watch.pauses, default=0.0) * 1e3!r} {scalars}"
          + (" call_s=" + ",".join(f"{t:.4f}" for t in counters["call_s"])
             if "call_s" in counters else ""), flush=True)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    line: dict = {
        "correct": checked > 0 and bad == 0,
        "attempted": driver.attempted,
        "failed": driver.failed,
    }
    if args.trace:
        trace = tracing.Trace.from_file(next(Path(trace_dir).rglob("*.xplane.pb")))
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = roofline.load_peaks(devices[0].device_kind) if on_chip else None
        run = Run(cell, config, traffic, counters, trace if on_chip else None, peaks, cell["chips"])
        metrics = {}
        for m in per_layer:
            value = spec.reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        if on_chip:
            chips = range(cell["chips"])
            device["busy_s"] = sum(trace.busy_s(d) for d in chips) / len(chips)
            device["window_s"] = trace.window_s
            line["breakdown"] = {"device_ops": trace.top_ops(0), "idle_gaps": trace.idle_by_host(0)}
    else:
        values = dict(end_to_end, setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                           for m in e2e}
    line["device"] = device
    line["checks"] = {"bad_answers": {"value": bad, "limit": 0}}
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    bench = spec.load_benchmark()
    chips = spec.cell(bench, args.workload)["chips"]
    devices = devices_or_none(chips, args.cpu_rehearsal)
    if devices is None:
        return NO_CHIP

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Every executable goes to the persistent cache, however fast it
    # compiled, so that a second run finds all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    line = execute(args, devices, compiles=compiles, t_start=T_START)
    checks = line["checks"]
    for name, c in checks.items():
        print(f"chipbench check: {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
