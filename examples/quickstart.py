"""Quickstart: the paper's parallel Quick Sort on the OHHC, end to end.

Runs the faithful algorithm (value-range buckets → per-processor local
sort → 3-phase hierarchical accumulation) on a 1-D full OHHC
(36 processors), validates the result, and prints the schedule facts the
paper proves analytically (Theorems 3/6).

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core import (
    AccumulationSchedule,
    OHHCTopology,
    SortEngine,
    ohhc_sort_host,
    ohhc_sort_sim,
)
from repro.data.distributions import ALL_DISTRIBUTIONS, make_array


def main():
    topo = OHHCTopology(d_h=1, variant="full")
    print(f"OHHC d_h=1 G=P: {topo.num_groups} groups × {topo.procs_per_group} "
          f"processors = {topo.total_procs} (Table 1.1)")

    x = make_array("random", 1 << 16, seed=0)

    # simulated-processor path; each processor's local sort is XLA's sort
    out, counts = ohhc_sort_sim(jnp.asarray(x), topo)
    assert np.array_equal(np.asarray(out), np.sort(x))
    print(f"sorted {x.size} ints; bucket imbalance max/mean = "
          f"{float(counts.max())/float(counts.mean()):.2f}")

    # schedule facts
    s = AccumulationSchedule.build(topo)
    print(f"Theorem 3 steps: paper formula={s.paper_step_count()}, "
          f"spanning-tree roundtrip={s.roundtrip_send_count()}")
    print(f"critical path rounds={s.critical_path_rounds()} "
          f"(= topology diameter 2·d_h+3 = {2*topo.d_h+3})")

    # full-size host path with per-bucket timing + comm model
    r = ohhc_sort_host(make_array("random", 1 << 20, seed=1), topo)
    print(f"1M-element host run: slowest bucket sort "
          f"{r.local_sort_times_s.max()*1e3:.2f} ms, modelled comm "
          f"{r.comm_model_time_s*1e3:.3f} ms, T_P={r.t_parallel_model_s*1e3:.2f} ms")

    # the unified engine: stats → path/method dispatch + capacity autotune
    # (DESIGN.md §4) — no hand-picked method or capacity anywhere.
    eng = SortEngine(topo)
    for dist in ALL_DISTRIBUTIONS:
        x = make_array(dist, 50_000, seed=2)
        out = eng.sort(x)
        assert np.array_equal(out, np.sort(x))
        rep = eng.last_report
        print(f"engine[{dist:>8}]: path={rep['plan'].path} "
              f"method={rep['plan'].method} "
              f"capacity={rep.get('capacity_used', '-')} "
              f"label={rep['stats'].label}")

    # batched traffic: one vmapped executable sorts the whole request batch
    outs = eng.sort_many([make_array("random", n, seed=n)
                          for n in (900, 1500, 2000)])
    assert all(np.all(np.diff(o) >= 0) for o in outs)
    print(f"sort_many: {len(outs)} requests, {eng.trace_count} total traces "
          f"this session (shape-bucketed warm cache)")


if __name__ == "__main__":
    main()
