"""sortd serving-layer benchmark (beyond paper, DESIGN.md §8).

Two measurements:

1. **Segmented-vs-loop throughput** — the acceptance gate for the fused
   batch path: for each batch size ``B``, sort the same ``B``
   variable-length arrays (a) with the pre-sortd per-array dispatch loop
   (``SortEngine.sort`` per array: per-request stats, plan, pad, device
   call, transfer) and (b) with ONE fused ``SortEngine.sort_segments``
   call.  The derived field ``ratio_vs_loop`` is loop-time / segmented-time
   (higher is better); the contract is ≥ 2.0 at ``B ≥ 64``.

2. **Service load generation** — drives a live :class:`repro.serve.sortd.Sortd`
   instance in two arrival modes and reports its own metrics:

   * *open-loop*: requests arrive on a fixed schedule at ``--rate`` req/s
     regardless of completion (the "millions of users" shape — arrival rate
     is an input, latency is the output; an overloaded server shows up as a
     growing p99, not a lower throughput);
   * *closed-loop*: ``--clients`` synchronous clients submit → wait →
     repeat (the benchmark-harness shape — throughput is the output and
     latency is bounded by the client count).

   Sizes mix across several shape buckets plus a slice of oversize
   requests (> ``max_bucket``) to exercise the direct fallback.

CSV rows carry p50/p99 latency (µs) and per-bucket pad waste; the full
machine-readable report (the CI artifact) is written as JSON — see
``benchmarks/README.md`` for how to read the columns.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks import common
from benchmarks.common import (
    DEFAULT_DTYPE,
    bench_rng,
    emit,
    measure_interleaved,
    resolve_dtype,
)
from repro.core import OHHCTopology, SortEngine
from repro.serve.sortd import Sortd, SortdConfig

LOOP_BATCH_SIZES = (16, 64, 256)
SMOKE_BATCH_SIZES = (16,)
PAPER_BATCH_SIZES = (64, 256, 1024)
LEN_RANGE = (256, 2048)  # per-request key counts for the throughput gate
ROUNDS = 3


def _make_batch(rng, B, dtype, lo=LEN_RANGE[0], hi=LEN_RANGE[1]):
    lens = rng.integers(lo, hi, B)
    return [rng.integers(0, 1 << 30, n).astype(dtype) for n in lens]


def _batch_sizes(paper: bool):
    if common.SMOKE:
        return SMOKE_BATCH_SIZES
    return PAPER_BATCH_SIZES if paper else LOOP_BATCH_SIZES


def _bench_segmented_vs_loop(paper: bool, dtype, report: dict) -> None:
    eng = SortEngine(OHHCTopology(1, "full"))
    rng = bench_rng(7)
    rows = {}
    for B in _batch_sizes(paper):
        arrs = _make_batch(rng, B, dtype)
        lens = [a.size for a in arrs]
        flat = np.concatenate(arrs)
        # warm both paths (compile) + correctness check once
        expect = [np.sort(a) for a in arrs]
        for got in (
            [eng.sort(a) for a in arrs],
            eng.sort_segments(flat, lens),
        ):
            for g, e in zip(got, expect):
                np.testing.assert_array_equal(g, e)
        # interleaved rounds (warmed above), median-of-ROUNDS with IQR —
        # the shared measurement contract (DESIGN.md §9)
        meas = measure_interleaved(
            {
                "loop": lambda: [eng.sort(a) for a in arrs],
                "segmented": lambda: eng.sort_segments(flat, lens),
            },
            warmup=0,
            repeats=ROUNDS,
        )
        t_loop, t_seg = meas["loop"].median_s, meas["segmented"].median_s
        ratio = t_loop / t_seg if t_seg > 0 else float("inf")
        rows[f"B{B}"] = {
            "batch": B,
            "loop_s": t_loop,
            "segmented_s": t_seg,
            "segmented_iqr_s": meas["segmented"].iqr_s,
            "ratio_vs_loop": ratio,
            "keys": int(flat.size),
        }
        emit(
            f"sortd/segmented/B{B}",
            t_seg * 1e6,
            f"ratio_vs_loop={ratio:.2f};loop_us={t_loop*1e6:.0f};"
            f"iqr_us={meas['segmented'].iqr_s * 1e6:.0f}",
        )
    report["throughput"] = rows


def _emit_service_metrics(mode: str, m: dict, wall_s: float, n_req: int) -> None:
    emit(
        f"sortd/{mode}/total",
        wall_s / max(n_req, 1) * 1e6,
        f"completed={m['completed']};p50_ms={m['latency_ms']['p50']:.2f};"
        f"p99_ms={m['latency_ms']['p99']:.2f};rps={n_req / wall_s:.0f}",
    )
    for bucket, b in sorted(m["buckets"].items()):
        emit(
            f"sortd/{mode}/{bucket}",
            b["p50_ms"] * 1e3,
            f"p99_ms={b['p99_ms']:.2f};pad_waste={b['pad_waste']:.3f};"
            f"mean_batch={b['mean_batch']:.1f}",
        )


def _request_stream(rng, n_req, dtype, max_bucket):
    """Mixed-size request generator: three bucket classes + ~2% oversize."""
    for i in range(n_req):
        r = rng.random()
        if r < 0.02:
            n = int(rng.integers(max_bucket + 1, max_bucket * 2))
        elif r < 0.50:
            n = int(rng.integers(64, 512))
        elif r < 0.85:
            n = int(rng.integers(512, 2048))
        else:
            n = int(rng.integers(2048, 4096))
        yield rng.integers(0, 1 << 30, n).astype(dtype)


def _bench_service(paper: bool, dtype, arrival: str, rate: float,
                   clients: int, report: dict) -> None:
    cfg = SortdConfig(max_batch=64, max_wait_s=0.005, max_bucket=1 << 12)
    n_req = 600 if paper else (40 if common.SMOKE else 200)
    modes = ("open", "closed") if arrival == "both" else (arrival,)
    for mode in modes:
        eng = SortEngine(OHHCTopology(1, "full"))
        rng = bench_rng(11)
        reqs = list(_request_stream(rng, n_req, dtype, cfg.max_bucket))
        # Warm the per-bucket executables on a throwaway service instance:
        # the engine's jit cache is shared, the measured instance's metrics
        # stay free of warm-up batch-of-1 traffic and compile stalls.
        with Sortd(eng, cfg) as warm:
            for x in reqs[:20]:
                warm.sort(x)
        with Sortd(eng, cfg) as sd:
            t0 = time.perf_counter()
            if mode == "open":
                period = 1.0 / rate
                futs = []
                for i, x in enumerate(reqs):
                    target = t0 + i * period
                    delay = target - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    futs.append(sd.submit(x))
                outs = [f.result(timeout=120) for f in futs]
            else:
                import threading

                outs = [None] * len(reqs)

                def client(cid):
                    for i in range(cid, len(reqs), clients):
                        outs[i] = sd.submit(reqs[i]).result(timeout=120)

                ts = [
                    threading.Thread(target=client, args=(c,))
                    for c in range(clients)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            wall = time.perf_counter() - t0
            # spot-check correctness on a slice (full check would dominate)
            for i in range(0, len(reqs), 29):
                np.testing.assert_array_equal(outs[i], np.sort(reqs[i]))
            m = sd.metrics()
        _emit_service_metrics(mode, m, wall, n_req)
        report[mode] = {
            "requests": n_req,
            "wall_s": wall,
            "rps": n_req / wall,
            "rate_target": rate if mode == "open" else None,
            "clients": clients if mode == "closed" else None,
            "metrics": m,
        }


def run(
    paper: bool = False,
    dtype: str = DEFAULT_DTYPE,
    *,
    arrival: str = "both",
    rate: float = 300.0,
    clients: int = 4,
    report: str | None = "sortd_report.json",
) -> dict:
    dt = resolve_dtype(dtype)
    doc: dict = {
        "suite": "sortd",
        "dtype": dtype,
        "config": {"arrival": arrival, "rate": rate, "clients": clients},
    }
    _bench_segmented_vs_loop(paper, dt, doc)
    if arrival != "none":
        _bench_service(paper, dt, arrival, rate, clients, doc)
    if report:
        with open(report, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# sortd report written: {report}", file=sys.stderr)
    return doc


if __name__ == "__main__":
    run(report=sys.argv[1] if len(sys.argv) > 1 else "sortd_report.json")
