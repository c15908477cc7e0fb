"""The one generator every traffic file feeds.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only.
Its ``kind`` names the loop that drives it (``chipbench/loops/<kind>.py``)
and its ``keys`` block names the key distribution
(``chipbench/keys/<distribution>.py``); the block's other entries are
that distribution's parameters.  Both are found by name, so a new loop
or a new distribution is a new file beside the others.

A distribution file defines ``draw(rng, n, dtype, **params)``, which
returns ``n`` keys of ``dtype`` drawn from the numpy generator ``rng``.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from chipbench import spec

# Streams drawn from one --seed.
KEYS = 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed works."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream])
    )


def sort_arrays(n: int, count: int, dtype: str, keys: dict, seed: int,
                root: Path | None = None) -> list:
    """``count`` arrays of ``n`` keys from the distribution ``keys`` names,
    each from a stream of its own."""
    params = dict(keys)
    draw = spec.load_named("keys", params.pop("distribution"), root).draw
    dt = np.dtype(dtype)
    return [draw(rng_for(seed, KEYS, i), n, dt, **params) for i in range(count)]
