"""What every loop shares.  The loops themselves live one per file in
``chipbench/loops/<kind>.py``, found by the ``kind`` a traffic file names.

A loop builds the system from the configuration, makes its inputs from
``--seed`` (set-up), runs the measured window, and keeps every answer the
window produced so that the reference can judge it after the window has
closed.  A loop file defines ``Loop``, a subclass of ``Driver``; the
harness marks ``window()`` with the ``chipbench.window`` span.
"""

from __future__ import annotations

import numpy as np

SPAN_CALL = "chipbench.sort_call"


def build_engine(config: dict, devices: list):
    from repro import compat
    from repro.core import SortEngine
    from repro.core.topology import OHHCTopology

    topo = OHHCTopology(config["topology"]["d_h"], config["topology"]["variant"])
    mesh_cfg = config.get("mesh")
    if not mesh_cfg:
        return SortEngine(topo)
    shape = tuple(mesh_cfg["shape"])
    axes = tuple(mesh_cfg["axes"])
    mesh = compat.make_mesh(shape, axes, devices=devices[: int(np.prod(shape))])
    return SortEngine(topo, mesh=mesh, axis_names=axes)


class Driver:
    """Common shape: ``setup``, ``window``, ``end_to_end``, ``close``,
    then ``answers`` for the reference and ``counters`` for the per-layer
    readers."""

    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float, devices: list,
                 root=None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.seconds, self.devices, self.root = float(seconds), devices, root
        self.counters: dict = {}
        self.attempted = 0
        self.failed = 0

    def setup(self) -> None:
        raise NotImplementedError

    def window(self) -> None:
        raise NotImplementedError

    def end_to_end(self) -> dict:
        raise NotImplementedError

    def answers(self):
        """``(request, answer)`` pairs of the window, ``answer`` None when
        it never came."""
        raise NotImplementedError

    def close(self) -> None:
        """Free the system's state, so the reference does not share it."""
