"""Benchmark harness — one module per paper table/figure family.

``PYTHONPATH=src python -m benchmarks.run [--paper|--smoke] [--suite NAME]
[--dtype D]``

Prints ``name,us_per_call,derived`` CSV with a ``# suite=<name>`` marker
line before each suite's rows.  ``--paper`` uses the paper's exact
10–60 MB sizes (slow on this 1-core container); the default grid is
1–4 MB with identical structure; ``--smoke`` shrinks every axis to the
wiring-validation slice ``tests/test_bench_smoke.py`` gates (numbers not
comparable to real runs).  ``--dtype`` selects the key type for the
suites that sweep the paper's "different integer array types" axis
(``engine``, ``verify``, ``sortd``); the rest pin the paper's int32.  The
``sortd`` suite additionally honours ``--arrival/--rate/--clients`` (load
generator shape) and ``--report`` (JSON report path); the ``fleet`` suite
honours ``--workers/--fleet-clients/--chaos/--no-chaos/--fleet-report`` —
see ``benchmarks/README.md``.
"""

from __future__ import annotations

import argparse

from benchmarks import (
    bench_commsteps,
    bench_counters,
    bench_efficiency,
    bench_engine,
    bench_faults,
    bench_fleet,
    bench_moe_dispatch,
    bench_netsim,
    bench_parallel,
    bench_sequential,
    bench_sortd,
    bench_speedup,
    bench_verify,
    bench_workloads,
)
from benchmarks import common
from benchmarks.common import DEFAULT_DTYPE, DTYPES
from repro.compile_cache import enable_compile_cache

SUITES = {
    "sequential": lambda a: bench_sequential.run(a.paper),  # Fig 6.1
    "parallel": lambda a: bench_parallel.run(a.paper),  # Figs 6.2/6.3
    "speedup_full": lambda a: bench_speedup.run(a.paper, "full"),  # 6.4–6.7
    "speedup_half": lambda a: bench_speedup.run(a.paper, "half"),  # 6.8–6.11
    "efficiency_full": lambda a: bench_efficiency.run(a.paper, "full"),  # 6.12–15
    "efficiency_half": lambda a: bench_efficiency.run(a.paper, "half"),  # 6.16–19
    "counters": lambda a: bench_counters.run(a.paper),  # 6.20–6.24
    "commsteps": lambda a: bench_commsteps.run(a.paper),  # Theorem 3
    "moe_dispatch": lambda a: bench_moe_dispatch.run(a.paper),
    "engine": lambda a: bench_engine.run(
        a.paper, dtype=a.dtype or DEFAULT_DTYPE
    ),  # autotuned dispatch
    "netsim": lambda a: bench_netsim.run(a.paper),  # link-level simulation
    "verify": lambda a: bench_verify.run(a.paper, dtype=a.dtype),  # conformance grid
    "sortd": lambda a: bench_sortd.run(  # serving layer (DESIGN.md §8)
        a.paper,
        dtype=a.dtype or DEFAULT_DTYPE,
        arrival=a.arrival,
        rate=a.rate,
        clients=a.clients,
        report=a.report,
    ),
    "fleet": lambda a: bench_fleet.run(  # multi-worker serving (DESIGN.md §10)
        a.paper,
        dtype=a.dtype or DEFAULT_DTYPE,
        workers=a.workers,
        clients=a.fleet_clients,
        chaos=a.chaos,
        report=a.fleet_report,
    ),
    "faults": lambda a: bench_faults.run(a.paper),  # degraded serving (§11)
    "workloads": lambda a: bench_workloads.run(a.paper),  # op layer (§12)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true", help="paper-exact 10-60MB sizes")
    ap.add_argument(
        "--smoke", action="store_true",
        help="wiring-validation slice: capped sizes, narrowed sweeps "
        "(tests/test_bench_smoke.py runs every suite this way; numbers are "
        "NOT comparable to real runs)",
    )
    ap.add_argument(
        "--only", "--suite", dest="only", default=None, choices=list(SUITES),
        help="run one suite (--suite is an alias)",
    )
    ap.add_argument(
        "--dtype", default=None, choices=list(DTYPES),
        help="key dtype for the dtype-swept suites (engine/sortd default to "
        f"{DEFAULT_DTYPE}; verify sweeps all dtypes unless narrowed)",
    )
    sortd = ap.add_argument_group("sortd suite")
    sortd.add_argument(
        "--arrival", default="both", choices=("open", "closed", "both", "none"),
        help="load-generator mode: open-loop (fixed arrival rate), "
        "closed-loop (N waiting clients), both, or none (throughput gate only)",
    )
    sortd.add_argument(
        "--rate", type=float, default=300.0,
        help="open-loop arrival rate in requests/s",
    )
    sortd.add_argument(
        "--clients", type=int, default=4,
        help="closed-loop concurrent client count",
    )
    sortd.add_argument(
        "--report", default="sortd_report.json",
        help="sortd JSON report path ('' disables)",
    )
    fleet = ap.add_argument_group("fleet suite")
    fleet.add_argument(
        "--workers", type=int, default=4,
        help="fleet worker count for the scaling comparison",
    )
    fleet.add_argument(
        "--fleet-clients", type=int, default=2,
        help="closed-loop clients for the fleet scaling gate (the "
        "latency-bound regime; --paper also sweeps c=8)",
    )
    fleet.add_argument(
        "--chaos", dest="chaos", action="store_true", default=True,
        help="run the chaos section (kill the busiest worker mid-load)",
    )
    fleet.add_argument("--no-chaos", dest="chaos", action="store_false")
    fleet.add_argument(
        "--fleet-report", default="fleet_report.json",
        help="fleet JSON report path ('' disables)",
    )
    args = ap.parse_args()
    if args.smoke and args.paper:
        ap.error("--smoke and --paper are mutually exclusive")
    if args.smoke:
        common.set_smoke(True)
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        # section marker (comment row): lets consumers attribute rows to
        # suites without pattern-matching the heterogeneous row names
        print(f"# suite={name}")
        fn(args)


if __name__ == "__main__":
    main()
