"""Host time per call in ``SortEngine.sort``'s pad stage, in ms: the sim
path's pad buffer and the copy into it, or the dist path's
shard-divisibility pad (span ``sort_engine.pad``)."""

from chipbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.stage_ms(run.trace, spans.PAD)
