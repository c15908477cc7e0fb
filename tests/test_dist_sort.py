"""shard_map distributed sort on 8 fake devices (subprocess: the main test
process must keep 1 device)."""

import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from repro.core.engine import join_prefixes

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


UNPACK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import threading
import numpy as np
from repro import compat
from repro.core import SortEngine
from repro.core.engine import SortPlan
from repro.data.distributions import make_array

def check(eng, x, writers, **kw):
    got = eng.sort(x, **kw)
    want = np.sort(x)
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), "np.sort"
    assert eng.last_report["unpack_writers"] == writers, eng.last_report
    return eng.last_report

# one writer per shard, n divisible by 4 and not (the shard pad is sliced off)
mesh = compat.make_mesh((4,), ("data",))
eng = SortEngine(mesh=mesh)
for n in (16384, 16387):
    check(eng, make_array("random", n, seed=n), 4)

# the forced-overflow retry joins the attempt that succeeded
n = 16384
rng = np.random.default_rng(7)
x = np.where(rng.random(n) < 0.9, rng.integers(0, 100, n),
             rng.integers(0, 2**31 - 1, n)).astype(np.int32)
rep = check(SortEngine(mesh=mesh, margin=0.0), x, 4,
            plan=SortPlan("dist", "paper", None, None, "forced"))
assert rep["overflow_retries"] == 1, rep

# over "data" of a (pod, data) mesh each shard is replicated over "pod" and
# copied once: two writers
mesh2 = compat.make_mesh((2, 2), ("pod", "data"))
check(SortEngine(mesh=mesh2, axis_names=("data",)), make_array("random", 16386, seed=1), 2)

# several callers on one engine at once share its writers
xs = [make_array("random", 16384, seed=s) for s in range(8)]
got = [None] * len(xs)
def call(i):
    got[i] = eng.sort(xs[i])
threads = [threading.Thread(target=call, args=(i,)) for i in range(len(xs))]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=120)
assert not any(t.is_alive() for t in threads)
assert all(g.tobytes() == np.sort(x).tobytes() for g, x in zip(got, xs))
print("DIST_UNPACK_OK")
"""


def test_engine_dist_unpack_writers_on_4_devices():
    r = subprocess.run(
        [sys.executable, "-c", UNPACK_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "DIST_UNPACK_OK" in r.stdout, r.stderr[-3000:]


@pytest.fixture(scope="module")
def join_pool():
    with ThreadPoolExecutor(4) as pool:
        yield pool


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32])
@pytest.mark.parametrize(
    "counts",
    [[6, 6, 6, 6], [1, 9, 4, 7], [5, 0, 3, 8], [10, 3, 10, 5], [7]],
    ids=["equal", "unequal", "zero", "whole", "single"],
)
def test_join_prefixes_matches_concatenate(join_pool, counts, dtype):
    rng = np.random.default_rng(len(counts) * 100 + sum(counts))
    parts = [(rng.random(10) * 1000).astype(dtype) for _ in counts]
    got, writers = join_prefixes(parts, np.asarray(counts), join_pool)
    want = np.concatenate([p[:c] for p, c in zip(parts, counts)])
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    assert got.flags.c_contiguous
    assert not any(np.shares_memory(got, p) for p in parts)
    assert writers == len(parts)


def test_join_prefixes_reads_device_parts(join_pool):
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    host = [rng.integers(0, 1 << 30, 512, dtype=np.int32) for _ in range(4)]
    counts = [512, 512, 300, 0]
    got, writers = join_prefixes([jnp.asarray(p) for p in host], counts, join_pool)
    want = np.concatenate([p[:c] for p, c in zip(host, counts)])
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    assert got.flags.writeable and got.flags.owndata
    assert writers == 4


class _Unreadable:
    dtype = np.dtype(np.int32)

    def __array__(self, *args, **kwargs):  # a writer reads its part to the host
        raise RuntimeError("unreadable shard")

    def __getitem__(self, key):
        raise RuntimeError("unreadable shard")


def test_join_prefixes_raises_a_writers_error(join_pool):
    parts = [np.arange(4, dtype=np.int32), _Unreadable()]
    with pytest.raises(RuntimeError, match="unreadable shard"):
        join_prefixes(parts, [4, 4], join_pool)


def test_join_prefixes_concurrent_callers_share_a_pool(join_pool):
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        rng = np.random.default_rng(0)
        parts = [rng.integers(0, 1 << 30, 5000, dtype=np.int32) for _ in range(4)]
        bad = []

        def caller(k):
            for r in range(50):
                counts = [(k * 7 + r * 13 + i * 997) % 5001 for i in range(4)]
                got, _ = join_prefixes(parts, counts, join_pool)
                want = np.concatenate([p[:c] for p, c in zip(parts, counts)])
                if got.tobytes() != want.tobytes():
                    bad.append((k, r))

        threads = [threading.Thread(target=caller, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert bad == []
    finally:
        sys.setswitchinterval(interval)
