"""Host time per call in ``SortEngine.sort``'s host-to-device copy, in ms:
``jnp.asarray`` of the padded keys, or the dist path's sharded
``device_put`` (span ``sort_engine.h2d``)."""

from chipbench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.stage_ms(run.trace, spans.H2D)
