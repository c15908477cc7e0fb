"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names every configuration, cell
and metric.  Each part lives in a file of its own, found by that name:

- a configuration: the ``file`` its entry gives (``chipbench/configs/``);
- a traffic mix: ``chipbench/traffic/<traffic>.json``, whose ``kind``
  names its loop, ``chipbench/loops/<kind>.py``, and whose ``keys`` name
  their distribution, ``chipbench/keys/<distribution>.py``;
- a per-layer metric: a reader ``chipbench/metrics/<name>.py``, or, for a
  name ``<base>.<variant>``, ``chipbench/metrics/<base>.py`` when the
  variant has no file of its own.

A configuration or a traffic file may carry a ``rehearsal`` block: the
entries it replaces for a CPU rehearsal at tiny size.

So a later change adds a configuration, a mix, a loop, a distribution or
a metric by adding files and entries, and edits no file that is already
here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "chipbench" / "traffic" / f"{name}.json").read_text())


def rehearsal(part: dict) -> dict:
    """``part`` with its ``rehearsal`` entries in place of its own."""
    return {**part, **part.get("rehearsal", {})}


def _in_cell(metric: dict, cell_name: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def metrics_for(bench: dict, cell_name: str) -> tuple[list, list]:
    """The cell's end-to-end metrics and its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, cell_name, ())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _in_cell(m, cell_name, names)]
    return e2e, layer


def load_named(kind: str, name: str, root: Path | None = None):
    """The module ``chipbench/<kind>/<name>.py`` under ``root``."""
    path = Path(root or ROOT) / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def loop(kind: str, root: Path = ROOT):
    """The ``Loop`` class that drives a traffic ``kind``."""
    return load_named("loops", kind, root).Loop


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of a per-layer metric."""
    base = Path(root) / "chipbench" / "metrics"
    if not (base / f"{name}.py").exists():
        name = name.split(".", 1)[0]
    return load_named("metrics", name, root).read
