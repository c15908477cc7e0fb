"""Per-call readings of the spans ``SortEngine.sort`` records under the
profiler.

The engine marks each call with a ``sort_engine.sort`` span and each of
its stages with a span nested inside it (``repro.core.engine``, the
``SPAN_*`` names; this module holds its own copy of those it reads, so
that it reads a program without them too).  The spans sit on the host
plane, on the device planes' clock.  A call is a ``sort_engine.sort``
span that lies whole inside the window; every reading here is a sum over
the window's calls divided by their count, in milliseconds.  A trace
with no such call, or with nothing to read inside the calls, reads
``None``.
"""

from __future__ import annotations

from chipbench.trace import clip, total, union

SORT = "sort_engine.sort"
PAD = "sort_engine.pad"
H2D = "sort_engine.h2d"
EXECUTE = "sort_engine.execute"
D2H = "sort_engine.d2h"


def calls(trace) -> list:
    """``(start, end)`` of the ``sort_engine.sort`` spans whole inside the
    window."""
    return [(s, e) for s, e in trace.spans(SORT) if trace.t0 <= s and e <= trace.t1]


def _within(intervals, outer) -> list:
    """The intervals that start inside one of ``outer``."""
    return [iv for iv in intervals if any(s <= iv[0] < e for s, e in outer)]


def stage_ms(trace, name: str) -> "float | None":
    """Host time in the spans ``name`` per call."""
    cs = calls(trace)
    stages = _within(trace.spans(name), cs)
    if not stages:
        return None
    return total(stages) * 1e-6 / len(cs)


def idle_ms(trace, name: str, device: int = 0) -> "float | None":
    """Time per call inside the spans ``name`` in which no op ran on
    ``device``."""
    cs = calls(trace)
    stages = union(_within(trace.spans(name), cs))
    if not stages:
        return None
    busy = trace.busy(device)
    idle = sum(e - s - total(clip(busy, s, e)) for s, e in stages)
    return idle * 1e-6 / len(cs)


def module_ms(trace, module: str, device: int = 0) -> "float | None":
    """Device time per call of the runs of executable ``module`` (its name
    on the ``XLA Modules`` line, before the fingerprint) that start inside
    a call."""
    cs = calls(trace)
    if not cs:
        return None
    runs = [(s, e) for s, e, n in trace.module_runs(device, inside=cs)
            if n.split("(", 1)[0] == module]
    if not runs:
        return None
    return total(runs) * 1e-6 / len(cs)
