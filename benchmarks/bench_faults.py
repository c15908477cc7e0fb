"""Degraded-topology gather benchmark — measured vs predicted slowdown
per fault scenario (beyond paper, DESIGN.md §11).

For each k-link fault scenario the engine can serve through
(``repro.net.faults``), two numbers from the same event-driven simulator:

* **predicted** — the barrier (BSP) accounting of the degraded schedule,
  the number the engine quotes in ``SortPlan.reason`` when it re-prices a
  plan under a fault scenario;
* **measured**  — the dependency-mode (contention-aware, overlapping)
  run of the *same* degraded schedule, i.e. what the modeled network
  actually does.

The derived column carries both slowdown ratios plus their agreement
(``measured / predicted``).  The in-bench gate pins the model contract:
a degraded gather must actually be slower (measured ≥ 1), the BSP
prediction must be conservative (measured ≤ predicted, within slack —
dependency mode overlaps rounds the barrier model serializes), and the
agreement must not collapse (a prediction several times the measured cost would make the
engine's quoted slowdowns meaningless).  Impossible scenarios (an
optically islanded group, a dead hub node) are emitted as rows too — the
typed ``GatherImpossible`` verdict with the offending node count is the
datum, and the engine's host fallback is the recorded behavior.

Wall-clock cost of the rebuild + simulation machinery is gated separately
by the ``faults`` perf suite (``repro.perf.suites`` → ``BENCH_faults.json``
via tools/perfguard.py); rows here are *simulated* gather seconds, which
are deterministic and machine-independent.
"""

from __future__ import annotations

from benchmarks import common
from benchmarks.common import emit

# Agreement band for measured/predicted (deterministic simulator output;
# the spread across topologies and chunk sizes is ~0.63-0.80).
AGREE_LO = 0.35
AGREE_SLACK = 1.05  # measured may exceed predicted by at most 5%


def _scenarios(topo):
    from repro.net.faults import FaultScenario

    return [
        FaultScenario.optical_link_down(1),
        FaultScenario.random_links(topo, 2, seed=3),
        FaultScenario.random_links(topo, 4, seed=3),
        FaultScenario.group_uplinks_down(topo, 1),
        FaultScenario.worker_down(1),
    ]


def run(paper: bool = False) -> dict:
    from repro.core.topology import OHHCTopology
    from repro.net.faults import GatherImpossible, predicted_slowdown

    n = 1 << 14 if common.SMOKE else (1 << 20 if paper else 1 << 16)
    dims = (1,) if common.SMOKE else (1, 2)
    doc: dict = {"suite": "faults", "n": n, "rows": {}}
    for d_h in dims:
        topo = OHHCTopology(d_h, "full")
        chunk = max(1, n // topo.total_procs)
        for sc in _scenarios(topo):
            key = f"faults/{sc.name}/d{d_h}"
            try:
                healthy_s, pred_s, pred = predicted_slowdown(
                    topo, sc, chunk_sizes=chunk, barrier=True
                )
                _, meas_s, meas = predicted_slowdown(
                    topo, sc, chunk_sizes=chunk, barrier=False
                )
            except GatherImpossible as e:
                # The typed refusal IS the result: the engine serves this
                # scenario on the host fallback (DESIGN.md §11).
                emit(
                    key,
                    0.0,  # no degraded gather exists to time
                    f"impossible;nodes={len(e.nodes)};fallback=host",
                )
                doc["rows"][key] = {
                    "impossible": True,
                    "nodes": sorted(e.nodes),
                }
                continue
            agree = meas / pred
            emit(
                key,
                meas_s * 1e6,
                f"pred_x={pred:.3f};meas_x={meas:.3f};agree={agree:.3f}",
            )
            doc["rows"][key] = {
                "impossible": False,
                "healthy_s": healthy_s,
                "predicted_s": pred_s,
                "measured_s": meas_s,
                "predicted_slowdown": pred,
                "measured_slowdown": meas,
                "agreement": agree,
            }
            if meas < 1.0 - 1e-9:
                raise RuntimeError(
                    f"{key}: degraded gather faster than healthy "
                    f"(measured x{meas:.3f}) — the fault injection is a no-op"
                )
            if agree > AGREE_SLACK:
                raise RuntimeError(
                    f"{key}: measured slowdown x{meas:.3f} exceeds the BSP "
                    f"prediction x{pred:.3f} by more than {AGREE_SLACK}x — "
                    "the quoted prediction is no longer conservative"
                )
            if agree < AGREE_LO:
                raise RuntimeError(
                    f"{key}: measured/predicted agreement {agree:.3f} below "
                    f"{AGREE_LO} — the predicted slowdown the engine quotes "
                    "has decoupled from the simulated network"
                )
    return doc


if __name__ == "__main__":
    run()
