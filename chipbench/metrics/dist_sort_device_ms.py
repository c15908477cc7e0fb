"""Device time per call of the dist sort executable, in ms: the
``jit_dist_sort`` runs on the first chip's ``XLA Modules`` line that start
inside a ``SortEngine.sort`` call."""

from chipbench import spans

MODULE = "jit_dist_sort"


def read(run):
    if run.trace is None:
        return None
    return spans.module_ms(run.trace, MODULE)
