"""``correct`` has to come out false when the answers are wrong.

Each test drives the rest of a run at CPU-rehearsal size, skipping only
the harness's look for a chip, with the timed path broken underneath:
the control (the reference one precision lower, float32 order of int32
keys) in the engine's place, an answer altered where it is produced,
half of an array left unsorted, and, across four devices, the exchange
between chips left out.  A sound run at the same size comes out correct.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import control, reference
from chipbench import run as bench_run

SEED = 2147483671


def altered(x):
    out = reference.reference_sort(x)
    if out.size:
        out[0] = out[0] + 1 if out[0] < np.iinfo(out.dtype).max else out[0] - 1
    return out


def half_left_out(x):
    out = reference.reference_sort(x)
    half = out.size // 2
    return np.concatenate([out[:half], np.asarray(x).ravel()[half:]])


@pytest.fixture
def engine_cls(monkeypatch):
    from repro.core.engine import SortEngine

    monkeypatch.setattr(SortEngine, "sort", SortEngine.sort)
    return SortEngine


def execute(cell, seconds=1.0):
    import jax

    args = bench_run.parse_args(["--workload", cell, "--seed", str(SEED), "--seconds",
                                 str(seconds), "--trace", "0", "--cpu-rehearsal"])
    return bench_run.execute(args, jax.devices())


@pytest.mark.parametrize("cell", ["sort_60mb_random"])
def test_sound_run_is_correct(cell):
    line = execute(cell)
    assert line["correct"] is True
    assert line["checks"]["bad_answers"] == {"value": 0, "limit": 0}


def test_control_fails_the_sort_cell(engine_cls):
    control.install(engine_cls)
    line = execute("sort_60mb_random")
    assert line["correct"] is False
    assert line["checks"]["bad_answers"]["value"] == line["attempted"] > 0


@pytest.mark.parametrize("fault", [altered, half_left_out], ids=["altered", "half_left_out"])
def test_broken_sort_fails_the_sort_cell(engine_cls, fault):
    control.install(engine_cls, fault)
    line = execute("sort_60mb_random")
    assert line["correct"] is False
    assert line["checks"]["bad_answers"]["value"] == line["attempted"]


MESH_SCRIPT = r"""
import json, sys
from pathlib import Path
import numpy as np
sys.path.insert(0, {repo!r})
from chipbench import control, reference
from chipbench import run as bench_run
from repro.core.engine import SortEngine
fault = {fault!r}
if fault == "control":
    control.install(SortEngine)
elif fault == "altered":
    def altered(x):
        out = reference.reference_sort(x)
        out[0] = out[0] + 1
        return out
    control.install(SortEngine, altered)
elif fault == "exchange_left_out":
    def no_exchange(self, x_np, plan, stats):
        shards = np.array_split(x_np, self.mesh.devices.size)
        self.last_report = None
        return np.concatenate([np.sort(s) for s in shards])
    SortEngine._sort_dist = no_exchange
import jax
args = bench_run.parse_args(["--workload", "dist_sort_60mb_mesh4", "--seed", "{seed}",
                             "--seconds", "1", "--trace", "0", "--cpu-rehearsal"])
print(json.dumps(bench_run.execute(args, jax.devices(), root=Path({root!r}))))
"""


@pytest.mark.parametrize("fault", ["none", "control", "altered", "exchange_left_out"])
def test_mesh_cell(fault, repo_root, mesh_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    script = MESH_SCRIPT.format(repo=str(repo_root), root=str(mesh_root), fault=fault, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", script], cwd=repo_root, env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (fault == "none")
