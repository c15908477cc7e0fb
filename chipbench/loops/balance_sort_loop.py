"""One caller sorting whole arrays back to back: the window of
``sort_loop``, unchanged, with the bucket balance of each call.

Traffic parameters as ``sort_loop``'s.  From each call's ``last_report``
it adds ``max_bucket_shares``: per call, ``max(counts) / n``, the share
of the call's keys that its fullest bucket held (sim and host paths both
report ``counts``).  A call whose report is missing or has no ``counts``
adds nothing; with no such call at all, the counter is left out.

The cell measures the sort on the chip.  A program whose planner sends
these keys to the host path puts no work on the device, so it cannot run
the cell: set-up stops with exit code 1 once the warm-up call reports a
``host`` plan, before any window.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from chipbench import spec

ROOT = Path(__file__).resolve().parents[2]
sort_loop = spec.load_named("loops", "sort_loop", ROOT)
# The engine wrapper that keeps each call's ``last_report``.
_Recorded = spec.load_named("loops", "mesh_sort_loop", ROOT)._Recorded


class Loop(sort_loop.Loop):
    def setup(self) -> None:
        super().setup()
        plan = (self.engine.last_report or {}).get("plan")
        if plan is not None and plan.path == "host":
            raise SystemExit(
                f"chipbench: this program plans {plan.path}/{plan.method} for the "
                f"configuration's keys ({plan.reason}); the device stays idle, so it "
                "cannot run this cell")
        self.engine = _Recorded(self.engine)

    def window(self) -> None:
        self.engine.reports.clear()
        super().window()
        shares = [int(np.max(r["counts"])) / r["n"] for r in self.engine.reports
                  if r and r.get("counts") is not None]
        if shares:
            self.counters["max_bucket_shares"] = shares
