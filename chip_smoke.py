"""Smoke run of the sort engine and the Sortd service on a TPU.

    python chip_smoke.py             # one chip: the sort, sortd and ops phases
    python chip_smoke.py --chips 4   # four chips: the mesh sorts and their references
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal   # tiny sizes, CPU

Drives ``SortEngine`` and ``Sortd`` once through their public entry points
at the paper's largest array size (2^24 int32 keys, 64 MiB), with keys
generated from a seed.  Every answer is checked against numpy; any mismatch
or exception ends the run with a non-zero exit and no result line.  Each
phase prints one line with its plan and its cold (compile included) and
warm seconds — smoke timings, not benchmark numbers.  The last line is one
JSON object naming the device.

Without a TPU the script exits non-zero before sorting anything, unless
``--cpu-rehearsal`` asks for the tiny-size CPU run.  ``--chips 4`` runs only
the multi-chip path: ``SortEngine(mesh=...)`` over a 4-device ``("data",)``
mesh (``paper``, ``sample``) and a ``(2, 2)`` ``("pod", "data")`` mesh
(``hier``), each compared with ``np.sort`` and the one-chip sort.  For a
CPU rehearsal of that path, set
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# Full sizes (the chip) and the CPU rehearsal's tiny ones.
SIZES = {
    "full": {"sort_n": 1 << 24, "requests": 512, "ops_n": 1 << 20, "merge_m": 1 << 12},
    "tiny": {"sort_n": 1 << 14, "requests": 64, "ops_n": 1 << 12, "merge_m": 1 << 6},
}
SORTD_CONFIG = dict(max_batch=32, max_wait_s=0.005, max_bucket=1 << 12)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def timed(fn):
    """Run ``fn`` twice: (first result, second result, cold s, warm s)."""
    t0 = time.perf_counter()
    first = fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = fn()
    warm = time.perf_counter() - t0
    return first, second, cold, warm


def report(phase: str, plan, cold: float, warm: float, extra: str = "") -> None:
    where = f"{plan.path}/{plan.method}" if plan is not None else "-"
    print(
        f"[{phase}] plan={where} cold_s={cold!r} warm_s={warm!r}"
        + (f" {extra}" if extra else ""),
        flush=True,
    )


def byte_equal(got, want) -> bool:
    got = np.asarray(got)
    return got.dtype == want.dtype and got.shape == want.shape and (
        got.tobytes() == want.tobytes()
    )


# ----------------------------------------------------------------- phases
def phase_sort(size: dict) -> None:
    from repro.core import SortEngine
    from repro.data.distributions import make_array

    eng = SortEngine()
    for dist in ("random", "sorted"):
        x = make_array(dist, size["sort_n"], seed=SEED, dtype=np.int32)
        want = np.sort(x)
        a, b, cold, warm = timed(lambda: eng.sort(x))
        plan = eng.last_report["plan"]
        check(plan.path == "sim", f"sort/{dist}: planned {plan.path}, not sim")
        check(byte_equal(a, want) and byte_equal(b, want), f"sort/{dist}: != np.sort")
        report(f"sort/{dist}/int32/n={x.size}", plan, cold, warm,
               f"capacity={eng.last_report['capacity_used']}")


def phase_sortd(size: dict) -> None:
    from repro.core import SortEngine
    from repro.serve import Sortd, SortdConfig
    from repro.serve.fleet.loadgen import request_mix

    eng = SortEngine()
    cfg = SortdConfig(**SORTD_CONFIG)
    for dtype in ("int32", "float32"):
        reqs = request_mix(size["requests"], dtype=dtype, seed=SEED)
        wants = [np.sort(r) for r in reqs]
        waves = {}
        for wave in ("cold", "warm"):
            with Sortd(eng, cfg) as sd:
                t0 = time.perf_counter()
                futs = [sd.submit(r) for r in reqs]
                outs = [f.result(timeout=600) for f in futs]
                waves[wave] = time.perf_counter() - t0
            m = sd.metrics()
            for i, (got, want) in enumerate(zip(outs, wants)):
                check(byte_equal(got, want), f"sortd/{dtype}: request {i} != np.sort")
            check(m["completed"] == len(reqs) and m["failed"] == 0,
                  f"sortd/{dtype}: completed={m['completed']} failed={m['failed']}")
        methods = {
            key: b["methods"] for key, b in sorted(m["buckets"].items()) if b["methods"]
        }
        used = set().union(*(set(v) for v in methods.values()))
        check(used == {"bitonic"}, f"sortd/{dtype}: flush methods {sorted(used)}")
        print(
            f"[sortd/{dtype}/requests={len(reqs)}] plan=sim/bitonic "
            f"cold_s={waves['cold']!r} warm_s={waves['warm']!r} "
            f"p50_ms={m['latency_ms']['p50']!r} p99_ms={m['latency_ms']['p99']!r} "
            f"oversize_direct={m['oversize_direct']} methods={json.dumps(methods)}",
            flush=True,
        )


def phase_ops(size: dict) -> None:
    from repro.core import SortEngine
    from repro.data.distributions import make_array

    eng = SortEngine()
    n = size["ops_n"]
    x = make_array("random", n, seed=SEED, dtype=np.int32)
    want = np.sort(x)

    k = n // 2  # kept set > n/4, so the head runs on the device
    a, b, cold, warm = timed(lambda: eng.top_k(x, k))
    plan = eng.last_report["plan"]
    check(plan.path == "sim", f"top_k: planned {plan.path}, not sim")
    check(byte_equal(a, want[:k]) and byte_equal(b, want[:k]), "top_k != np.sort head")
    report(f"top_k/int32/n={n}/k={k}", plan, cold, warm)

    vals = make_array("random", n, seed=SEED + 1, dtype=np.int32)

    def pairs():
        ks, vs = eng.sort_pairs(x, vals)
        return np.asarray(ks), np.asarray(vs)

    a, b, cold, warm = timed(pairs)
    plan = eng.last_report["plan"]
    check(plan.path == "sim", f"sort_pairs: planned {plan.path}, not sim")
    ref = np.lexsort((vals, x))
    for ks, vs in (a, b):
        check(byte_equal(ks, want), "sort_pairs: keys != np.sort")
        # equal keys may carry their payloads in any order: compare the
        # (key, payload) multisets
        out = np.lexsort((vs, ks))
        check(byte_equal(vs[out], vals[ref]), "sort_pairs: payloads lost or re-paired")
    report(f"sort_pairs/int32/n={n}", plan, cold, warm)

    a, b, cold, warm = timed(lambda: eng.argsort_keys(x))
    plan = eng.last_report["plan"]
    check(plan.path == "sim", f"argsort_keys: planned {plan.path}, not sim")
    for ks, perm in (a, b):
        check(byte_equal(ks, want), "argsort_keys: keys != np.sort")
        check(np.array_equal(x[perm], ks), "argsort_keys: keys[perm] != sorted keys")
        check(np.array_equal(np.sort(perm), np.arange(n)), "argsort_keys: not a permutation")
    report(f"argsort_keys/int32/n={n}", plan, cold, warm)

    new = make_array("random", size["merge_m"], seed=SEED + 2, dtype=np.int32)
    merged_want = np.sort(np.concatenate([want, new]))
    a, b, cold, warm = timed(lambda: eng.merge_sorted(want, new))
    inner = eng.last_report["inner_plan"]
    check(inner is not None and inner.path == "sim", "merge_sorted: increment not on sim")
    check(byte_equal(a, merged_want) and byte_equal(b, merged_want),
          "merge_sorted != np.sort of the union")
    report(f"merge_sorted/int32/buf={n}/new={new.size}", inner, cold, warm)


def phase_dist(x: np.ndarray, one_chip: np.ndarray) -> None:
    import jax

    from repro import compat
    from repro.core import SortEngine, SortPlan

    devices = jax.devices()[:4]
    want = np.sort(x)
    flat = compat.make_mesh((4,), ("data",), devices=devices)
    pods = compat.make_mesh((2, 2), ("pod", "data"), devices=devices)
    for method, mesh, axes in (
        ("paper", flat, ("data",)),
        ("sample", flat, ("data",)),
        ("hier", pods, ("pod", "data")),
    ):
        eng = SortEngine(mesh=mesh, axis_names=axes)
        plan = SortPlan("dist", method, None, None, "chip smoke: every dist method")
        a, b, cold, warm = timed(lambda: eng.sort(x, plan=plan))
        check(eng.last_report["plan"].path == "dist", f"dist/{method}: not on dist")
        for got in (a, b):
            check(byte_equal(got, want), f"dist/{method}: != np.sort")
            check(byte_equal(got, one_chip), f"dist/{method}: != one-chip sort")
        report(
            f"dist/{method}/mesh={dict(mesh.shape)}/int32/n={x.size}",
            eng.last_report["plan"], cold, warm,
            f"retries={eng.last_report['overflow_retries']}",
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run at tiny sizes on whatever backend JAX has (no TPU needed)",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {platform!r}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has {len(devices)} devices",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.data.distributions import make_array

    cache_dir = enable_compile_cache()
    size = SIZES["tiny" if args.cpu_rehearsal else "full"]
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind} "
          f"({platform}), jax {jax.__version__}, compile cache {cache_dir}",
          flush=True)
    if args.chips == 4:
        from repro.core import SortEngine

        x = make_array("random", size["sort_n"], seed=SEED, dtype=np.int32)
        one = SortEngine()
        a, _, cold, warm = timed(lambda: one.sort(x))
        check(byte_equal(a, np.sort(x)), "one-chip sort != np.sort")
        report(f"sort/random/int32/n={x.size}", one.last_report["plan"], cold, warm)
        phase_dist(x, a)
    else:
        phase_sort(size)
        phase_sortd(size)
        phase_ops(size)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
