"""Host time per call in ``SortEngine.sort``'s device-to-host copy, in ms:
the sorted keys and the bucket counts back to numpy (span
``sort_engine.d2h``)."""

from chipbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.stage_ms(run.trace, spans.D2H)
