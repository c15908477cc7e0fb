"""The traffic generator: deterministic per seed, its key distributions
found by name, and rehearsal sizes taken from the files themselves."""

import numpy as np
import pytest

from chipbench import generate as gen
from chipbench import spec

SEEDS = (0, 7, 2**31 + 5, 2**33 + 1)
RANDOM = {"distribution": "random"}


@pytest.mark.parametrize("seed", SEEDS)
def test_sort_arrays_repeat_per_seed(seed):
    a = gen.sort_arrays(1 << 12, 3, "int32", RANDOM, seed)
    b = gen.sort_arrays(1 << 12, 3, "int32", RANDOM, seed)
    assert len(a) == 3 and all(np.array_equal(x, y) for x, y in zip(a, b))


def test_sort_arrays_differ_across_seeds_and_arrays():
    runs = [gen.sort_arrays(1 << 12, 2, "int32", RANDOM, s) for s in SEEDS]
    for r in runs[1:]:
        assert not np.array_equal(r[0], runs[0][0])
    assert not np.array_equal(runs[0][0], runs[0][1])


def test_random_keys_span_int32():
    a = gen.sort_arrays(1 << 16, 3, "int32", RANDOM, 2**31 + 1)
    assert all(x.dtype == np.int32 and x.size == 1 << 16 and x.min() >= 0 for x in a)
    assert max(int(x.max()) for x in a) > 2**30


@pytest.mark.parametrize("dtype", ["int32", "uint32", "int64"])
def test_random_keys_keep_their_dtype(dtype):
    (x,) = gen.sort_arrays(1000, 1, dtype, RANDOM, 3)
    assert x.dtype == np.dtype(dtype) and x.min() >= 0


def test_the_cells_traffic_names_a_distribution_that_exists():
    traffic = spec.traffic("random")
    assert spec.load_named("keys", traffic["keys"]["distribution"]).draw
    assert spec.loop(traffic["kind"]) is not None


def test_unknown_distribution_is_refused():
    with pytest.raises(FileNotFoundError):
        gen.sort_arrays(10, 1, "int32", {"distribution": "zipf"}, 0)


def test_unknown_loop_is_refused():
    with pytest.raises(FileNotFoundError):
        spec.loop("no_such_loop")


def test_distribution_parameters_reach_the_draw(tmp_path):
    keys = tmp_path / "chipbench" / "keys"
    keys.mkdir(parents=True)
    (keys / "constant.py").write_text(
        "import numpy as np\n\n"
        "def draw(rng, n, dtype, value):\n"
        "    return np.full(n, value, dtype)\n"
    )
    (x,) = gen.sort_arrays(5, 1, "int32", {"distribution": "constant", "value": 9}, 1, tmp_path)
    np.testing.assert_array_equal(x, np.full(5, 9, np.int32))


@pytest.mark.parametrize("part,want", [
    ({"n": 100, "rehearsal": {"n": 4}}, 4),
    ({"n": 100}, 100),
])
def test_rehearsal_entries_replace_their_own(part, want):
    assert spec.rehearsal(part)["n"] == want


def test_every_part_of_the_benchmark_has_a_rehearsal_size(repo_root):
    bench = spec.load_benchmark(repo_root)
    for cell in bench["workloads"]:
        config = spec.config(bench, cell["config"], repo_root)
        traffic = spec.traffic(cell["traffic"], repo_root)
        assert spec.rehearsal(config)["n"] <= 1 << 14
        assert spec.rehearsal(traffic)["arrays"] <= 2
