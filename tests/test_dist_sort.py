"""shard_map distributed sort on 8 fake devices (subprocess: the main test
process must keep 1 device)."""

import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import dist_sort, host_check_globally_sorted
from repro.data.distributions import make_array

from repro import compat

mesh = compat.make_mesh((8,), ("data",))
def exact(v, c, n8):
    vals = np.asarray(v).reshape(8, -1); cc = np.asarray(c).ravel()
    return np.concatenate([np.sort(vals[i])[:cc[i]] for i in range(8)])

for dist in ["random", "sorted", "reversed", "local"]:
    x = make_array(dist, 8192, seed=3)
    for method in ["sample", "paper"]:
        cf = 8.0  # sorted input sends a whole shard to one destination row
        v, c = dist_sort(jnp.asarray(x), mesh=mesh, axis_names=("data",),
                         method=method, capacity_factor=cf)
        got = exact(v, c, 8192)
        if method == "sample" or dist != "local":
            assert np.array_equal(got, np.sort(x)), (dist, method)
        else:
            # paper splitters under clustered values overflow capacity —
            # detectable as dropped elements, never silent corruption
            assert host_check_globally_sorted(np.asarray(v), np.asarray(c))

mesh2 = compat.make_mesh((2, 4), ("pod", "data"))
x = make_array("random", 8192, seed=5)
v, c = dist_sort(jnp.asarray(x), mesh=mesh2, axis_names=("pod", "data"),
                 method="hier", capacity_factor=8.0)
assert np.array_equal(exact(v, c, 8192), np.sort(x)), "hier"

# uint32 keys at full range: the hier stage-2 fill must stay typed (a bare
# python-int sentinel weak-types to int32 and overflows at trace time).
xu = make_array("random", 8192, seed=6, dtype=np.uint32)
v, c = dist_sort(jnp.asarray(xu), mesh=mesh2, axis_names=("pod", "data"),
                 method="hier", capacity_factor=8.0)
assert np.array_equal(exact(v, c, 8192), np.sort(xu)), "hier uint32"

# Valiant two-hop routing: sorted input at capacity_factor=2 — the direct
# route drops 3/4 of the data (send skew), valiant keeps all of it.
xs = make_array("sorted", 8192, seed=3)
v, c = dist_sort(jnp.asarray(xs), mesh=mesh, axis_names=("data",),
                 method="sample", capacity_factor=2.0)
assert int(np.asarray(c).sum()) < 8192, "expected direct-route overflow"
v, c = dist_sort(jnp.asarray(xs), mesh=mesh, axis_names=("data",),
                 method="valiant", capacity_factor=2.0)
assert np.array_equal(exact(v, c, 8192), np.sort(xs)), "valiant"
print("DIST_SORT_SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_dist_sort_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd="/root/repo",
    )
    assert "DIST_SORT_SUBPROCESS_OK" in r.stdout, r.stderr[-3000:]


ENGINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro import compat
from repro.core import SortEngine
from repro.core.dist_sort import row_capacity
from repro.core.engine import SortPlan
from repro.data.distributions import make_array

mesh = compat.make_mesh((4,), ("data",))
one_chip = SortEngine()

def check(eng, x, **kw):
    got = eng.sort(x, **kw)
    rep = eng.last_report
    want = np.sort(x)
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), "np.sort"
    assert got.tobytes() == one_chip.sort(x).tobytes(), "one-chip sort"
    assert sum(rep["shard_counts"]) == x.size, rep["shard_counts"]
    assert all(isinstance(c, int) and c >= 0 for c in rep["shard_counts"])
    return rep

# uniform keys, n divisible by 4 and not (the shard pad is left out):
# cf 2, so 2 * ceil(shard / 4) slots a row, rounded up to a multiple of 8
for n, capacity in ((16384, 2048), (16387, 2056)):
    x = make_array("random", n, seed=n)
    rep = check(SortEngine(mesh=mesh), x)
    assert rep["plan"].method == "paper" and rep["overflow_retries"] == 0
    assert rep["dist_capacity"] == row_capacity(n + (-n) % 4, 4, 2.0) == capacity, rep
    assert len(rep["shard_counts"]) == 4

# a forced overflow: 90% of the keys in the first of four equal-width
# ranges overflows rows of half a shard; the retry doubles cf to 4
n = 16384
rng = np.random.default_rng(7)
x = np.where(rng.random(n) < 0.9, rng.integers(0, 100, n),
             rng.integers(0, 2**31 - 1, n)).astype(np.int32)
rep = check(SortEngine(mesh=mesh, margin=0.0), x,
            plan=SortPlan("dist", "paper", None, None, "forced"))
assert rep["overflow_retries"] == 1, rep
assert rep["dist_capacity"] == row_capacity(n, 4, 4.0) == n // 4, rep

# hier over (2, 2) sizes its two stages' rows itself: no flat capacity
mesh2 = compat.make_mesh((2, 2), ("pod", "data"))
x = make_array("random", 16386, seed=1)
rep = check(SortEngine(mesh=mesh2, axis_names=("pod", "data")), x)
assert rep["plan"].method == "hier" and rep["dist_capacity"] is None, rep

# sorting over "data" alone: each shard is replicated over "pod", and the
# host copies each shard once
rep = check(SortEngine(mesh=mesh2, axis_names=("data",)), x)
assert rep["plan"].method == "paper" and len(rep["shard_counts"]) == 2, rep
print("DIST_COUNTERS_OK")
"""


def test_engine_dist_counters_on_4_devices():
    r = subprocess.run(
        [sys.executable, "-c", ENGINE_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "DIST_COUNTERS_OK" in r.stdout, r.stderr[-3000:]
