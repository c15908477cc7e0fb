"""The control: the reference, one precision lower, in the program's place.

    python3 -m chipbench.control --workload <name> --seed <n> --seconds <s>

Runs a cell exactly as ``chipbench.run`` does, at its own size and load,
with ``SortEngine.sort`` replaced by ``reference.control_sort`` (int32
keys ordered by their float32 value).  The loop around the engine runs
unchanged.  The line it prints must read ``"correct": false``: its
``bad_answers`` is the upper reading that the limit of 0 sits below
(``PERF.md``).  The benchmark's own runs never call this.
"""

from __future__ import annotations

import sys

import numpy as np

from chipbench import reference
from chipbench import run as bench_run


def install(engine_cls, broken_sort=reference.control_sort) -> None:
    """Replace the engine's sort entry point with ``broken_sort``."""

    def sort(self, x, *, plan=None):
        self.last_report = None
        return broken_sort(np.asarray(x).ravel())

    engine_cls.sort = sort


def main(argv=None) -> int:
    from repro.core.engine import SortEngine

    install(SortEngine)
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
