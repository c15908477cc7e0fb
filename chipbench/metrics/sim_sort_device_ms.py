"""Device time per call of the sim sort executable, in ms: the
``jit_sim_sort`` runs on the first chip's ``XLA Modules`` line that start
inside a ``SortEngine.sort`` call."""

from chipbench import spans

MODULE = "jit_sim_sort"


def read(run):
    if run.trace is None:
        return None
    return spans.module_ms(run.trace, MODULE)
