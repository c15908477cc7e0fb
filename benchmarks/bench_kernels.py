"""Kernel microbenchmarks (beyond paper): bitonic local sort and bucket
count/rank vs their jnp oracles — wall time on CPU (interpret mode for the
Pallas path, so the oracle comparison is about correctness-per-cost; on
TPU the kernel path is the fast one)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit, measure_interleaved, time_call
from repro.data.distributions import make_array
from repro.kernels import batched, ops, ref

# Row-backend A/B shapes: serving buckets (B requests × padded row
# length).  Smoke shrinks to a wiring check.
ROWSORT_SHAPES = ((64, 1024), (64, 4096))
ROWSORT_SMOKE_SHAPES = ((8, 256),)


def _rowsort_ab(paper: bool) -> None:
    """Interleaved A/B of the segment-path row backends (DESIGN.md §8):
    vmapped XLA ``jnp.sort`` (the engine's default) vs the fused Pallas
    batched kernel (both compare-exchange variants) on identical
    full-range int32 batches.
    """
    interpret = ops._auto_interpret(None)
    shapes = ROWSORT_SMOKE_SHAPES if common.SMOKE else ROWSORT_SHAPES
    for B, L in shapes:
        rng = common.bench_rng(L)
        info = np.iinfo(np.int32)
        x = jnp.asarray(
            rng.integers(info.min, info.max, (B, L), dtype=np.int32)
        )
        lens = jnp.full((B,), L, jnp.int32)
        vmap_fn = jax.jit(jax.vmap(jnp.sort))
        fns = {
            "vmap": lambda: vmap_fn(x),
            "pallas": lambda: batched.batched_row_sort(
                x, lens, method="bitonic", interpret=interpret
            ),
            "pallas2op": lambda: batched.batched_row_sort(
                x, lens, method="bitonic2op", interpret=interpret
            ),
        }
        meas = measure_interleaved(fns, warmup=1, repeats=5)
        t_vmap = meas["vmap"].median_s
        for name, m in meas.items():
            ratio = t_vmap / m.median_s if m.median_s > 0 else float("inf")
            emit(
                f"kernels/rowsort_{name}/B{B}xL{L}",
                m.median_s * 1e6,
                f"vs_vmap={ratio:.2f};iqr_us={m.iqr_s * 1e6:.0f}",
            )


def run(paper: bool = False) -> None:
    for n in (4096,) if common.SMOKE else (4096, 65536):
        x = jnp.asarray(make_array("random", n, seed=n))
        sort_ref = jax.jit(jnp.sort)
        t_ref = time_call(lambda: sort_ref(x).block_until_ready())
        emit(f"kernels/jnp_sort/{n}", t_ref * 1e6, "oracle")
        t_k = time_call(lambda: ops.local_sort(x).block_until_ready())
        emit(f"kernels/bitonic_interpret/{n}", t_k * 1e6, "pallas-interpret")

    _rowsort_ab(paper)

    m = common.smoke_scaled(65536)
    ids = jnp.asarray(make_array("random", m, seed=1) % 64, jnp.int32)
    t_ref = time_call(
        lambda: jax.jit(ref.ref_bucket_count_rank, static_argnums=1)(ids, 64)[0]
        .block_until_ready()
    )
    emit(f"kernels/count_rank_ref/{m}x64", t_ref * 1e6, "jnp")
    t_k = time_call(lambda: ops.bucket_count_rank(ids, 64)[0].block_until_ready())
    emit(f"kernels/count_rank_pallas/{m}x64", t_k * 1e6, "pallas-interpret")


if __name__ == "__main__":
    run()
