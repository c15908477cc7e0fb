"""One caller calling ``SortEngine.sort`` back to back (closed loop),
rotating through a few whole arrays made in set-up.

Traffic parameters: ``arrays`` (how many distinct arrays) and ``keys``
(the key distribution, ``chipbench/keys/<distribution>.py``).  The
configuration gives ``n``, ``dtype``, the topology and an optional mesh.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import generate as gen
from chipbench.drivers import SPAN_CALL, Driver, build_engine


class Loop(Driver):
    def setup(self) -> None:
        cfg, tr = self.config, self.traffic
        self.engine = build_engine(cfg, self.devices)
        self.arrays = gen.sort_arrays(cfg["n"], tr["arrays"], cfg["dtype"], tr["keys"],
                                      self.seed, self.root)
        for x in self.arrays:  # one call per array: every plan compiles here
            self.engine.sort(x)
        self.trace_count0 = self.engine.trace_count

    def window(self) -> None:
        eng, arrays = self.engine, self.arrays
        self.calls: list = []  # (array index, answer, capacity or None, seconds)
        plans, retries = set(), 0
        t0 = t_call = time.perf_counter()
        while True:
            i = len(self.calls) % len(arrays)
            with jax.profiler.TraceAnnotation(SPAN_CALL):
                out = eng.sort(arrays[i])
            rep = eng.last_report or {}
            plan = rep.get("plan")
            plans.add(f"{plan.path}/{plan.method}" if plan else "none")
            retries += rep.get("overflow_retries", 0)
            now = time.perf_counter()
            self.calls.append((i, out, rep.get("capacity_used"), now - t_call))
            t_call = now
            if now - t0 >= self.seconds:
                break
        self.elapsed = time.perf_counter() - t0
        self.attempted = len(self.calls)
        n = self.config["n"]
        P = eng.topo.total_procs
        self.counters = {
            "calls": len(self.calls),
            "keys": len(self.calls) * n,
            "itemsize": np.dtype(self.config["dtype"]).itemsize,
            "pad_shares": [1.0 - n / (P * c) for _, _, c, _ in self.calls if c],
            "call_s": [t for *_, t in self.calls],
            "trace_count_delta": eng.trace_count - self.trace_count0,
            "plans": ",".join(sorted(plans)),
            "overflow_retries": retries,
        }

    def end_to_end(self) -> dict:
        return {"sort_keys_per_s": self.counters["keys"] / self.elapsed / 1e6}

    def close(self) -> None:
        del self.engine

    def answers(self):
        for i, out, *_ in self.calls:
            yield self.arrays[i], out
