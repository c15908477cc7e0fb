"""One caller sorting whole arrays over a mesh back to back: the window of
``sort_loop``, unchanged, with the dist path's counters of each call.

Traffic parameters as ``sort_loop``'s.  From each call's ``last_report``
it adds:

- ``slot_pad_shares``: ``1 - n / (shards² · dist_capacity)`` per call, the
  share of the exchange's slots (each of ``shards`` sources sends
  ``shards`` rows of ``dist_capacity``) that hold no key;
- ``shard_counts``: per call, the caller's keys each shard received.

A call whose report is missing or has no ``dist_capacity`` (a program
without these counters) adds to neither; with no such call at all, the
counters are left out.
"""

from __future__ import annotations

from pathlib import Path

from chipbench import spec

sort_loop = spec.load_named("loops", "sort_loop", Path(__file__).resolve().parents[2])


class _Recorded:
    """The engine, keeping the ``last_report`` of each ``sort`` call."""

    def __init__(self, engine):
        self.engine = engine
        self.reports: list = []

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def sort(self, x, **kwargs):
        out = self.engine.sort(x, **kwargs)
        self.reports.append(self.engine.last_report)
        return out


class Loop(sort_loop.Loop):
    def setup(self) -> None:
        super().setup()
        self.engine = _Recorded(self.engine)

    def window(self) -> None:
        self.engine.reports.clear()
        super().window()
        reps = [r for r in self.engine.reports if r and r.get("dist_capacity")]
        if reps:
            self.counters["slot_pad_shares"] = [
                1.0 - r["n"] / (len(r["shard_counts"]) ** 2 * r["dist_capacity"]) for r in reps
            ]
            self.counters["shard_counts"] = [r["shard_counts"] for r in reps]
