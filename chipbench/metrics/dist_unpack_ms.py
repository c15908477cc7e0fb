"""Host time per call in the dist path's unpack, in ms: the gathered
shards split at their counts and their valid keys joined into one array
(span ``sort_engine.unpack``)."""

from chipbench import spans

UNPACK = "sort_engine.unpack"


def read(run):
    if run.trace is None:
        return None
    return spans.stage_ms(run.trace, UNPACK)
