"""SortEngine dispatch benchmark (beyond paper): autotuned vs fixed methods.

For every input class (the paper's four + duplicate-heavy) and size, times

* ``auto``  — ``SortEngine.sort`` with full stats→dispatch→capacity autotune
  (DESIGN.md §4), and
* ``fixed/<method>`` — the pre-engine calling convention: the same executor
  with a hand-picked method and the legacy ``2·ceil(n/P)`` capacity (the
  engine's overflow-escalation keeps it *correct* on skewed inputs, so the
  fixed baselines pay their recompile/retry cost honestly).

The acceptance bar: ``auto`` within 10% of the best fixed method on every
scenario (it should usually *be* the best fixed method, minus the guessing).
Derived CSV fields carry ``ratio_vs_best_fixed`` per scenario.

Timing: configs are measured round-robin via ``measure_interleaved``
(warm-up drift hits every config equally) and the reported value is the
median of ``ROUNDS`` with the IQR in the derived field — the shared
measurement contract (DESIGN.md §9).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import (
    DEFAULT_DTYPE,
    emit,
    measure_interleaved,
    n_for_mb,
    resolve_dtype,
    sizes_mb,
)
from repro.core import (
    OHHCTopology,
    SortEngine,
    SortPlan,
    bucketed_length,
    default_capacity,
    x64_enabled,
)
from repro.data.distributions import ALL_DISTRIBUTIONS, make_array

FIXED_METHODS = ("paper", "sampled")
ROUNDS = 3


def _fixed_plan(eng: SortEngine, n: int, method: str, dtype) -> SortPlan:
    """What callers did before the engine: fixed method, heuristic capacity."""
    if n >= eng.host_threshold or (np.dtype(dtype).itemsize == 8 and not x64_enabled()):
        # 64-bit keys have no exact jit path without x64 — the fixed
        # baseline must take the same host detour the engine does.
        return SortPlan("host", method, None, None, "fixed baseline")
    padded = bucketed_length(n)
    cap = default_capacity(padded, eng.topo.total_procs)
    return SortPlan("sim", method, cap, padded, "fixed baseline")


def run(paper: bool = False, dtype: str = DEFAULT_DTYPE) -> dict:
    topo = OHHCTopology(1, "full")
    eng = SortEngine(topo)
    dt = resolve_dtype(dtype)
    # int32 keeps the historical CSV row names; other dtypes tag the rows.
    tag = "" if dtype == DEFAULT_DTYPE else f"/{dtype}"
    out = {}
    for dist in ALL_DISTRIBUTIONS:
        for mb in sizes_mb(paper):
            n = n_for_mb(mb)
            x = make_array(dist, n, seed=mb, dtype=dt)
            expect = np.sort(x)

            configs = {"auto": None}
            configs.update({m: _fixed_plan(eng, n, m, dt) for m in FIXED_METHODS})
            # warm every executable + check correctness once per config
            retries = {}
            for name, fp in configs.items():
                got = eng.sort(x) if fp is None else eng.sort(x, plan=fp)
                assert np.array_equal(got, expect), (name, dist, mb)
                retries[name] = eng.last_report["overflow_retries"]
                if fp is None:
                    plan = eng.last_report["plan"]
            # interleaved rounds (already warmed above): drift hits every
            # config equally instead of whichever was timed first
            meas = measure_interleaved(
                {
                    name: (lambda fp=fp: eng.sort(x) if fp is None
                           else eng.sort(x, plan=fp))
                    for name, fp in configs.items()
                },
                warmup=0,
                repeats=ROUNDS,
            )
            times = {name: m.median_s for name, m in meas.items()}

            for m in FIXED_METHODS:
                emit(
                    f"engine/fixed-{m}/{dist}/{mb}MB{tag}",
                    times[m] * 1e6,
                    f"path={configs[m].path};retries={retries[m]};"
                    f"iqr_us={meas[m].iqr_s * 1e6:.1f}",
                )
            best = min(times[m] for m in FIXED_METHODS)
            ratio = times["auto"] / best if best > 0 else 1.0
            out[(dist, mb)] = {**times, "ratio": ratio}
            emit(
                f"engine/auto/{dist}/{mb}MB{tag}",
                times["auto"] * 1e6,
                f"path={plan.path};method={plan.method};"
                f"ratio_vs_best_fixed={ratio:.2f};"
                f"iqr_us={meas['auto'].iqr_s * 1e6:.1f}",
            )
    return out


if __name__ == "__main__":
    run()
