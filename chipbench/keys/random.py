"""Uniform keys over ``[0, dtype max)``: the paper's ``random`` array (§5),
as ``repro.data.distributions.make_array("random")`` draws it for int32."""

import numpy as np


def draw(rng, n, dtype):
    return rng.integers(0, np.iinfo(dtype).max, n, dtype=dtype)
