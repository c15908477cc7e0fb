"""Device-idle time per call inside ``SortEngine.sort``'s execute spans,
in ms, on the first chip: the executable's dispatch, the separate
counts-reduce executable and the sync that waits for the counts, summed
over an overflow's retries (span ``sort_engine.execute``, ops of the
``XLA Ops`` line)."""

from chipbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.idle_ms(run.trace, spans.EXECUTE)
