"""The plain reference that decides ``correct``, and its control.

The reference is numpy's sort: the configuration's guarantee is an exact
ascending sort, so an answer is right when it is byte-equal to
``np.sort`` of the request.  It imports nothing of the program.

The control breaks that guarantee the way a tempting shortcut would:
ordering int32 keys by their float32 value, which cannot tell apart keys
that differ below float32's 24-bit mantissa.  It returns a permutation of
the input, so only the order is wrong.
"""

from __future__ import annotations

import numpy as np


def reference_sort(keys: np.ndarray) -> np.ndarray:
    return np.sort(np.asarray(keys).ravel(), kind="stable")


def control_sort(keys: np.ndarray) -> np.ndarray:
    """The reference one precision lower: a stable sort on float32 keys."""
    keys = np.asarray(keys).ravel()
    return keys[np.argsort(keys.astype(np.float32), kind="stable")]


def same_answer(got, want: np.ndarray) -> bool:
    if got is None:
        return False
    got = np.asarray(got)
    if got.dtype != want.dtype or got.shape != want.shape:
        return False
    as_bytes = lambda a: np.ascontiguousarray(a).view(np.uint8)  # noqa: E731
    return bool(np.array_equal(as_bytes(got), as_bytes(want)))
