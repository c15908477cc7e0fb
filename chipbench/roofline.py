"""Peak table and the bytes any sort must move.

The peaks live in ``peaks.json`` beside this file, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default.

A sort's roofline counts only the bytes that every implementation has to
move: each valid key read once and written once.  Padding, capacity slack
and extra passes are not counted, so the share reads the same work
whatever implements the sort.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def sort_bytes(n_keys: int, itemsize: int) -> int:
    """Least HBM traffic of sorting ``n_keys`` keys: read once, write once."""
    return 2 * int(n_keys) * int(itemsize)


def hbm_roofline_pct(n_bytes: float, seconds: float, peaks: dict) -> "float | None":
    """Share of the HBM roofline, in percent: the least time the bytes take
    at peak bandwidth over the device time they took.  ``None`` when no
    time was measured."""
    if seconds <= 0 or n_bytes <= 0:
        return None
    return 100.0 * (n_bytes / peaks["hbm_bytes_per_s"]) / seconds
