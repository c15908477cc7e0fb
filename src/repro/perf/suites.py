"""The gated perf scenarios, one registry per bench suite (DESIGN.md §9).

Each entry mirrors an existing ``benchmarks/`` suite — ``engine``,
``sortd``, ``netsim``, ``verify``, ``fleet`` — but pinned to a small,
deterministic slice sized for a CI gate: the point is a *stable judged
number per case*, not figure-quality coverage (that stays in
``benchmarks/run.py``).  Every case builds its inputs and warms its
executables inside ``setup`` so the timed call measures steady-state work
only, and every RNG draw is seeded.

Work models (``Workload``) are honest lower bounds — inputs read once,
outputs written once, ``n·log2(n)`` comparison "flops" for a sort — so
``pct_of_roofline`` is comparable across cases and the normalized ratio is
portable across hosts (see ``repro.perf.normalize``).  The netsim suite
has no bytes-moved model (its cost is simulator events), so it opts out
and is judged on raw seconds, machine-local by declaration.
"""

from __future__ import annotations

import math

import numpy as np

from repro.perf.normalize import Workload
from repro.perf.schema import PerfCase

SUITE_NAMES = (
    "engine", "sortd", "netsim", "verify", "fleet", "faults",
    "workloads",
)


def _sort_workload(n: int, itemsize: int) -> Workload:
    return Workload(
        bytes_moved=2.0 * n * itemsize,
        flops=float(n) * math.log2(max(n, 2)),
    )


# --- engine ---------------------------------------------------------------


def _engine_setup(dist: str, n: int, dtype: str):
    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array

        eng = SortEngine()
        x = make_array(dist, n, seed=n, dtype=np.dtype(dtype))
        return lambda: eng.sort(x)

    return setup


def engine_cases(*, smoke: bool = True) -> "list[PerfCase]":
    cells = [("random", 65536, "int32", True), ("dupes", 65536, "int32", True)]
    if not smoke:
        cells += [
            ("random", 262144, "int32", False),
            ("local", 65536, "int32", False),
            ("random", 65536, "uint32", False),
        ]
    return [
        PerfCase(
            suite="engine",
            key=f"sort/{dist}/{n}/{dtype}",
            setup=_engine_setup(dist, n, dtype),
            workload=_sort_workload(n, np.dtype(dtype).itemsize),
            smoke=in_smoke,
        )
        for dist, n, dtype, in_smoke in cells
    ]


# --- sortd ----------------------------------------------------------------


def _segments_setup(batch: int, lo: int, hi: int, dtype: str):
    def setup():
        from repro.core import SortEngine

        eng = SortEngine()
        rng = np.random.default_rng(7)
        lens = rng.integers(lo, hi, batch)
        arrs = [rng.integers(0, 1 << 30, n).astype(dtype) for n in lens]
        flat = np.concatenate(arrs)
        seg_lens = [int(a.size) for a in arrs]
        return lambda: eng.sort_segments(flat, seg_lens)

    return setup


def sortd_cases(*, smoke: bool = True) -> "list[PerfCase]":
    cells = [(64, True)]
    if not smoke:
        cells += [(256, False)]
    out = []
    for batch, in_smoke in cells:
        # mean segment length (lo+hi)/2 sizes the work model; the draw is
        # seeded, so the realized total is fixed per case anyway.
        lo, hi = 256, 2048
        total = batch * (lo + hi) // 2
        out.append(PerfCase(
            suite="sortd",
            key=f"sort_segments/B{batch}/int32",
            setup=_segments_setup(batch, lo, hi, "int32"),
            workload=_sort_workload(total, 4),
            smoke=in_smoke,
        ))
    return out


# --- netsim ---------------------------------------------------------------


def _netsim_setup(dims: tuple, chunk_elems: int):
    def setup():
        from repro.net.report import netsim_report

        return lambda: netsim_report(dims=dims, chunk_elems=chunk_elems)

    return setup


def netsim_cases(*, smoke: bool = True) -> "list[PerfCase]":
    cells = [((1,), 256, True)]
    if not smoke:
        cells += [((1, 2), 1024, False)]
    return [
        PerfCase(
            suite="netsim",
            key=f"report/d{'-'.join(map(str, dims))}/chunk{chunk}",
            setup=_netsim_setup(dims, chunk),
            workload=None,  # event-loop cost; raw-seconds fallback
            # Raw seconds on a pure-python event loop swing ~2x run to
            # run (GC, allocator state); the band is wide by declaration.
            lower=0.70,
            upper=1.50,
            smoke=in_smoke,
        )
        for dims, chunk, in_smoke in cells
    ]


# --- fleet ----------------------------------------------------------------


def _fleet_loop_setup(workers: "int | None", n_req: int, clients: int):
    """Closed-loop drive of a persistent warm service; ``workers=None``
    means the single-Sortd baseline (shipped default config)."""

    def setup():
        from repro.core import SortEngine
        from repro.serve.fleet import FleetConfig, SortdFleet
        from repro.serve.fleet.loadgen import drive_closed_loop, request_mix
        from repro.serve.sortd import Sortd, SortdConfig

        reqs = request_mix(n_req, seed=11)
        if workers is None:
            svc = Sortd(SortEngine(), SortdConfig(max_queue=4096))
        else:
            # Lax heartbeat: on a 1-core host the workers' cold first
            # flushes (jit compiles) contend and can each stall >1s; the
            # case measures the steady-state loop, not failover, so a
            # compile pause must not get a worker declared dead mid-warmup.
            svc = SortdFleet(
                FleetConfig(workers=workers, heartbeat_timeout_s=10.0)
            )
        # warm every bucket's executable on every worker; the service stays
        # live across the timed repeats (daemon threads, process-lifetime)
        drive_closed_loop(svc.submit, request_mix(60, seed=3), clients=clients)
        return lambda: drive_closed_loop(svc.submit, reqs, clients=clients)

    return setup


def fleet_cases(*, smoke: bool = True) -> "list[PerfCase]":
    # Paired cases on the SAME mix/clients: the baseline file's raw_s
    # ratio (single / w4) documents the fleet's ≥2x scaling contract at
    # c=2, and perfguard re-judges each side on every gate run.  Timing is
    # cross-thread scheduling, not device work — no honest bytes/flops
    # model — so the cases opt out of normalization and carry the wide
    # netsim-style band.
    n_req, clients = (80, 2) if smoke else (240, 2)
    band = {"lower": 0.70, "upper": 1.50}
    return [
        PerfCase(
            suite="fleet",
            key=f"closed/single/c{clients}",
            setup=_fleet_loop_setup(None, n_req, clients),
            workload=None,
            **band,
        ),
        PerfCase(
            suite="fleet",
            key=f"closed/w4/c{clients}",
            setup=_fleet_loop_setup(4, n_req, clients),
            workload=None,
            **band,
        ),
    ]


# --- faults ---------------------------------------------------------------


def _fault_predict_setup(d_h: int, n: int):
    """The degraded-plan pricing machinery end to end: schedule rebuild
    under the faulted router + both simulator accountings (the work
    ``SortEngine._comm_price`` does once per (bucket, scenario))."""

    def setup():
        from repro.core.topology import OHHCTopology
        from repro.net.faults import FaultScenario, predicted_slowdown

        topo = OHHCTopology(d_h, "full")
        sc = FaultScenario.optical_link_down(1)
        chunk = max(1, n // topo.total_procs)

        def run():
            predicted_slowdown(topo, sc, chunk_sizes=chunk, barrier=True)
            predicted_slowdown(topo, sc, chunk_sizes=chunk, barrier=False)

        return run

    return setup


def _fault_sort_setup(n: int, dtype: str):
    """Steady-state degraded serving: a warm engine with an active fault
    scenario sorting on the re-priced sim path (plan + comm caches hot, so
    the timed call is the sort itself — the §11 contract that degraded
    mode costs planning once, not per request)."""

    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array
        from repro.net.faults import FaultScenario

        eng = SortEngine()
        eng.set_fault_scenario(FaultScenario.optical_link_down(1))
        x = make_array("random", n, seed=n, dtype=np.dtype(dtype))
        return lambda: eng.sort(x)

    return setup


def faults_cases(*, smoke: bool = True) -> "list[PerfCase]":
    # Python event-loop + rebuild cost on one side, jit sort on the other;
    # both judged raw-seconds with the wide netsim-style band (the pricing
    # case is pure-python, and the sort case's fault overhead is cache
    # lookups — normalization would just mirror the engine suite).
    band = {"lower": 0.70, "upper": 1.50}
    cases = [
        PerfCase(
            suite="faults",
            key="predict/optical_g1/d1/n65536",
            setup=_fault_predict_setup(1, 65536),
            workload=None,
            **band,
        ),
        PerfCase(
            suite="faults",
            key="sort/degraded/optical_g1/random/65536/int32",
            setup=_fault_sort_setup(65536, "int32"),
            workload=_sort_workload(65536, 4),
        ),
    ]
    return cases


# --- workloads ------------------------------------------------------------


def _topk_setup(n: int, k: int):
    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array

        eng = SortEngine()
        x = make_array("random", n, seed=n)
        eng.top_k(x, k)  # warm the per-(capacity, keep) executable
        return lambda: eng.top_k(x, k)

    return setup


def _fullsort_setup(n: int):
    """The full-sort half of the top-k pair — same seeded input, so the
    committed raw_s ratio IS the skip-rule margin perfguard re-judges."""

    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array

        eng = SortEngine()
        x = make_array("random", n, seed=n)
        eng.sort(x)
        return lambda: eng.sort(x)

    return setup


def _merge_tick_setup(n_buf: int, n_new: int):
    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array

        eng = SortEngine()
        buf = np.sort(make_array("random", n_buf, seed=3))
        new = make_array("random", n_new, seed=4)
        eng.merge_sorted(buf, new)
        return lambda: eng.merge_sorted(buf, new)

    return setup


def _pairs_pytree_setup(n: int):
    def setup():
        from repro.core import SortEngine
        from repro.data.distributions import make_array

        eng = SortEngine()
        keys = make_array("random", n, seed=5)
        idx = np.arange(n, dtype=np.int64)
        vals = {"idx": idx, "nested": (keys.astype(np.float64),)}
        eng.sort_pairs(keys, vals)
        return lambda: eng.sort_pairs(keys, vals)

    return setup


def _moe_dispatch_setup(dispatch: str):
    def setup():
        import jax
        import jax.numpy as jnp

        from repro.configs.base import MoEConfig, ModelConfig
        from repro.models import moe as MOE
        from repro.models.common import NO_SHARD

        cfg = ModelConfig(
            family="moe", d_model=256, dtype=jnp.bfloat16,
            moe=MoEConfig(
                num_experts=8, num_experts_per_tok=2, expert_d_ff=512,
                dispatch=dispatch, capacity_factor=1.25,
            ),
        )
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 256), jnp.bfloat16)
        f = jax.jit(lambda x: MOE.apply_moe(p, x, cfg, NO_SHARD)[0])
        f(x).block_until_ready()
        return lambda: f(x).block_until_ready()

    return setup


def workloads_cases(*, smoke: bool = True) -> "list[PerfCase]":
    """The §12 workload layer, gated as paired rows.

    ``topk`` and ``fullsort`` share the same seeded input: the committed
    ``raw_s`` ratio between them is the skip-rule speedup the issue gates
    (top-k must beat a full sort at n≥4096, k≤n/16 — the hard fail lives
    in ``benchmarks/bench_workloads.py``; here perfguard re-judges each
    side against its own baseline every run).  Host-path ops (top-k's
    numpy head, the merge gather) are microsecond-scale python+numpy —
    raw-seconds with the wide band, no device work model.
    """
    band = {"lower": 0.70, "upper": 1.50}
    n = 65536
    cases = [
        PerfCase(
            suite="workloads",
            key=f"topk/random/{n}/k{n // 16}",
            setup=_topk_setup(n, n // 16),
            workload=None,
            **band,
        ),
        PerfCase(
            suite="workloads",
            key=f"fullsort/random/{n}",
            setup=_fullsort_setup(n),
            workload=_sort_workload(n, 4),
        ),
        PerfCase(
            suite="workloads",
            key="merge_tick/buf65536/new2048",
            setup=_merge_tick_setup(65536, 2048),
            workload=None,
            **band,
        ),
        PerfCase(
            suite="workloads",
            key="pairs_pytree/random/4096",
            setup=_pairs_pytree_setup(4096),
            workload=_sort_workload(4096, 4),
            **band,
        ),
    ]
    if not smoke:
        cases += [
            PerfCase(
                suite="workloads",
                key=f"moe_dispatch/{dispatch}/E8k2T512",
                setup=_moe_dispatch_setup(dispatch),
                workload=None,
                smoke=False,
                **band,
            )
            for dispatch in ("sorted", "argsort")
        ]
    return cases


# --- verify ---------------------------------------------------------------


def _verify_setup(dtype: str):
    def setup():
        from repro.verify import differential, grid

        scenarios = [sc for sc in grid.tier1_grid() if sc.dtype == dtype]
        engines = differential.EngineCache(devices=1)
        run = lambda: differential.run_grid(  # noqa: E731
            scenarios, keep_outputs=False, engines=engines
        )
        run()  # warm every (shape bucket, method) executable in the slice
        return run

    return setup


def _verify_workload(dtype: str) -> Workload:
    from repro.verify import grid

    total_bytes = 0.0
    total_flops = 0.0
    for sc in grid.tier1_grid():
        if sc.dtype != dtype:
            continue
        w = _sort_workload(sc.n, np.dtype(sc.dtype).itemsize)
        total_bytes += w.bytes_moved
        total_flops += w.flops
    return Workload(bytes_moved=total_bytes, flops=total_flops)


def verify_cases(*, smoke: bool = True) -> "list[PerfCase]":
    dtypes = ["int32"] if smoke else ["int32", "uint32"]
    return [
        PerfCase(
            suite="verify",
            key=f"tier1/{dtype}",
            setup=_verify_setup(dtype),
            workload=_verify_workload(dtype),
            smoke=dtype == "int32",
        )
        for dtype in dtypes
    ]


SUITES = {
    "engine": engine_cases,
    "sortd": sortd_cases,
    "netsim": netsim_cases,
    "verify": verify_cases,
    "fleet": fleet_cases,
    "faults": faults_cases,
    "workloads": workloads_cases,
}


def cases_for(suite: str, *, smoke: bool = True) -> "list[PerfCase]":
    if suite not in SUITES:
        raise KeyError(f"unknown perf suite {suite!r}; choose from {SUITE_NAMES}")
    return SUITES[suite](smoke=smoke)
