"""From a profiler trace to device time: busy union, idle share, module and
op time, collectives, and what the host did while the device was idle.

``extract(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain dict, keeping what the reduction needs:

- on each ``/device:TPU:<i>`` plane, the ``XLA Modules`` line (one event
  per executable run, named ``<module>(<fingerprint>)``) and the
  ``XLA Ops`` line (one event per HLO instruction, named by its text).
  Copies between host and device are not on these lines: they run on the
  host's transfer threads, so device time here excludes them;
- on the host plane, every event of every thread line.

``Trace`` works on that dict, so a trimmed extract committed as a test
fixture is reduced by the same code as a fresh trace.  Times are in
nanoseconds on the profiler's common clock; the benchmark marks its
window with a ``chipbench.window`` span on the host.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"

# HLO opcodes that move data between chips.
COLLECTIVE_OPCODES = (
    "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
    "send", "recv",
)
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")
_OPNAME = re.compile(r"^%([^ ]+) = ")
_SHAPE = re.compile(r" = (\(?[a-z0-9]+\[[0-9,]*\])")


def extract(path) -> dict:
    """Read one ``.xplane.pb`` into ``{"planes": [{name, lines: [{name,
    events: [[start_ns, dur_ns, name], ...]}]}]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        if not on_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.start_ns, e.duration_ns, e.name] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def opcode(op_text: str) -> str:
    m = _OPCODE.search(op_text)
    return m.group(1) if m else op_text.split(" ", 1)[0]


def op_label(op_text: str) -> str:
    """A short stable label for an HLO op: ``opcode result-shape %name``."""
    name = _OPNAME.match(op_text)
    shape = _SHAPE.search(op_text)
    parts = [opcode(op_text)]
    if shape:
        parts.append(shape.group(1).lstrip("("))
    if name:
        parts.append("%" + name.group(1))
    return " ".join(parts)


def is_collective(op_text: str) -> bool:
    code = opcode(op_text)
    name = _OPNAME.match(op_text)
    names = (code, name.group(1) if name else "")
    return any(n.startswith(c) for n in names for c in COLLECTIVE_OPCODES)


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


class Trace:
    """Reductions over an extract, restricted to the benchmark's window."""

    def __init__(self, data: dict):
        self.ops: dict[int, list] = {}
        self.modules: dict[int, list] = {}
        self.host: list = []  # (start, end, name, line)
        for plane in data["planes"]:
            m = DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"]:
                evs = [(float(s), float(s) + float(d), n) for s, d, n in line["events"]]
                if m is None:
                    self.host.extend((s, e, n, line["name"]) for s, e, n in evs)
                elif line["name"] == OPS_LINE:
                    self.ops.setdefault(int(m.group(1)), []).extend(evs)
                elif line["name"] == MODULES_LINE:
                    self.modules.setdefault(int(m.group(1)), []).extend(evs)
        windows = self.spans(WINDOW_SPAN)
        if not windows:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        self.t0, self.t1 = windows[0]

    @classmethod
    def from_file(cls, path) -> "Trace":
        return cls(extract(path))

    # ------------------------------------------------------------ host side
    def spans(self, name: str) -> list:
        """``(start, end)`` of every host span called ``name``, in order."""
        return sorted((s, e) for s, e, n, _ in self.host if n == name)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    # ---------------------------------------------------------- device side
    def busy(self, device: int) -> list:
        """Disjoint intervals in which an op ran on ``device``, in the window."""
        return union(clip(((s, e) for s, e, _ in self.ops.get(device, ())), self.t0, self.t1))

    def busy_s(self, device: int) -> float:
        return total(self.busy(device)) * 1e-9

    def idle_share(self, device: int) -> float:
        return 1.0 - self.busy_s(device) / self.window_s

    def op_time_s(self, device: int, pred) -> float:
        """Device time of the ops whose text satisfies ``pred``, in the
        window, overlaps merged."""
        ivs = ((s, e) for s, e, n in self.ops.get(device, ()) if pred(n))
        return total(union(clip(ivs, self.t0, self.t1))) * 1e-9

    def module_runs(self, device: int, inside=None) -> list:
        """``(start, end, name)`` of the executable runs that start in the
        window, or inside one of the host spans ``inside`` when given."""
        runs = [r for r in self.modules.get(device, ()) if self.t0 <= r[0] < self.t1]
        if inside is not None:
            runs = [r for r in runs if any(s <= r[0] < e for s, e in inside)]
        return runs

    # ------------------------------------------------------------ breakdown
    def top_ops(self, device: int, k: int = 10) -> list:
        """The ``k`` ops that took most device time in the window."""
        by: dict[str, float] = {}
        for s, e, n in self.ops.get(device, ()):
            lo, hi = max(s, self.t0), min(e, self.t1)
            if hi > lo:
                label = op_label(n)
                by[label] = by.get(label, 0.0) + (hi - lo) * 1e-9
        return sorted(([n, t] for n, t in by.items()), key=lambda p: -p[1])[:k]

    def idle_by_host(self, device: int, k: int = 10, ignore=(WINDOW_SPAN,),
                     resolution_ns: float = 10_000.0) -> list:
        """Idle time on ``device`` in the window, split by what the host was
        doing meanwhile: each idle stretch goes to the most specific host
        event over it (the shortest one, on any thread), summed per event
        name.  The ``k`` names with most idle seconds, ``[name, seconds]``;
        idle time under no host event is ``"host: no event"``."""
        import numpy as np

        bins = int(np.ceil((self.t1 - self.t0) / resolution_ns))
        if bins <= 0:
            return []
        idle = np.ones(bins, bool)
        for s, e in self.busy(device):
            idle[int((s - self.t0) // resolution_ns): int(np.ceil((e - self.t0) / resolution_ns))] = False
        label = np.full(bins, -1, np.int64)
        names: dict[str, int] = {}
        # longest first, so that the shorter, more specific event paints last
        for s, e, n, _ in sorted(self.host, key=lambda h: h[0] - h[1]):
            if n in ignore or e <= self.t0 or s >= self.t1:
                continue
            lo = max(0, int((s - self.t0) // resolution_ns))
            hi = min(bins, int(np.ceil((e - self.t0) / resolution_ns)))
            if hi > lo:
                label[lo:hi] = names.setdefault(n, len(names))
        counts = np.bincount(label[idle] + 1, minlength=len(names) + 1)
        by_name = {"host: no event": counts[0]}
        by_name.update({n: counts[i + 1] for n, i in names.items()})
        out = [[n, float(c) * resolution_ns * 1e-9] for n, c in by_name.items() if c]
        return sorted(out, key=lambda p: -p[1])[:k]
