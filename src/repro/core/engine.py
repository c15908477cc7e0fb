"""Unified autotuned sort engine — the single entry point over the three
execution paths (DESIGN.md §4).

The repo has three faithful implementations of the paper's parallel Quick
Sort — ``ohhc_sort_sim`` (jit/vmap simulated processors), ``ohhc_sort_host``
(paper-scale numpy with the Theorem-6 comm model) and ``dist_sort``
(``shard_map`` over a real device mesh) — each with its own method knob
(``paper``/``sampled``/``sample``/``hier``/``valiant``) and a bucket
``capacity`` the caller had to guess.  ``SortEngine`` removes the guessing:

1. **Stats inspection** (``estimate_stats``): a strided ≤1 k sample yields
   ``sortedness`` (asc-pair minus desc-pair fraction), ``skew`` (max/mean of
   an equal-width histogram — the quantity that breaks the paper's Array
   Division Procedure), the top-duplicate fraction, and the *measured* max
   bucket fraction under each splitter rule.  The labels map onto the
   paper's §5 input taxonomy (random / sorted / reversed / local) plus the
   beyond-paper duplicate-heavy class.

2. **Dispatch** (``choose_plan``): stats × topology → execution path and
   method.  The full decision table is DESIGN.md §4; the shape is
   *mesh → dist (hier > valiant > sampled > paper), huge → host (exact
   ragged buckets), large and skewed past the balanced capacity floor of
   its method → host, else → sim* (skewed inputs with ``sampled``
   splitters).

3. **Capacity autotune** (``autotune_capacity``): instead of the fixed
   ``2·ceil(n/P)`` heuristic, capacity comes from the measured max bucket
   fraction plus a 3σ binomial sampling-error term and a safety margin,
   clamped below by the legacy heuristic (which is also the deterministic
   answer for balanced inputs, keeping the jit cache warm) and quantized to
   powers of two above it.  ``sort`` verifies the returned counts and
   escalates capacity ×2 on the (rare) overflow, so the answer is always
   exact.

4. **Warm jit cache**: compiled executables are keyed on
   ``(pow2 size bucket, capacity, method, dtype, P)``; inputs are padded to
   the bucket and the valid length is passed as a *traced* scalar, so
   repeated traffic of nearby sizes never recompiles.  ``trace_count``
   exposes actual retraces for tests and monitoring.

Batched entry points: ``sort_segments`` fuses many variable-length arrays
into ONE padded ``(B, Lbucket)`` vmapped device call (worst-row stats and
capacity measured in one vectorized pass — the device-side foundation of
the ``repro.serve.sortd`` micro-batching service, DESIGN.md §8);
``sort_many`` is its list-of-arrays wrapper; ``sort_pairs`` is the
key/payload sort (multi-operand ``lax.sort``) behind
``repro.serve.engine.ServeEngine``'s length-ordering hot path.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import TYPE_CHECKING, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import partition, workloads
from repro.core.ohhc_sort import ohhc_sort_host
from repro.core.topology import OHHCTopology
from repro.core.workloads import TopKTooLarge

if TYPE_CHECKING:
    from concurrent.futures import Executor, ThreadPoolExecutor

# Granularity cap for stats histograms: coarser than P only ever
# *over*-estimates the max bucket fraction (refining buckets can't raise it).
_MAX_STAT_BUCKETS = 256

# Largest row bucket the segmented batch path sorts with the direct
# sentinel-padded row sort (plan method ``"bitonic"``: no capacity, no
# overflow) instead of the P-way bucket machinery (see choose_batch_plan).
SEGMENT_BITONIC_MAX = 1 << 13

# Largest input the sim path takes by default; ``host_threshold`` is one
# past it.  n ≤ 2^24 covers the paper's 10–60 MB int32 arrays and pads to
# at most the 2^24 shape bucket, whose int32 ``paper`` executable a
# described-v5e compile sizes (memory_analysis) at a 64 MiB argument plus
# 0.30 GB of temporaries (the bucket-id sort's operands, the padded rows,
# the unscatter buffer), under 2% of one chip's 16 GB of HBM.  The 2^25
# bucket needs 0.60 GB; whether the device still pays off there is for a
# chip measurement to say.  Larger inputs take the exact numpy host path.
SIM_MAX_N = 1 << 24
HOST_THRESHOLD = SIM_MAX_N + 1

# The sim path copies a result bucket of at least two pieces' bytes to the
# host as equal pieces, one per D2H_PIECE_BYTES, at most D2H_MAX_PIECES,
# each copied and joined by a thread of its own (``d2h_pieces``).  One
# thread copying a 64 MiB result untiles it into fresh pages at ≈0.8 GB/s;
# on a TPU v5e host the 2^24 int32 bucket's D2H and join took 83 ms as one
# piece, 84 as 2, 36 as 4, 27 as 8 and 26 as 16 (medians of 15).
D2H_PIECE_BYTES = 8 << 20
D2H_MAX_PIECES = 8


def d2h_pieces(padded_n: int, dtype) -> int:
    """The pieces the sim path copies a ``padded_n`` bucket of ``dtype`` to
    the host in: one per :data:`D2H_PIECE_BYTES`, at most
    :data:`D2H_MAX_PIECES`, a power of two that divides ``padded_n``; 1 for
    a bucket under two pieces' bytes."""
    k = min(D2H_MAX_PIECES, padded_n * np.dtype(dtype).itemsize // D2H_PIECE_BYTES)
    if k < 2:
        return 1
    return math.gcd(1 << (k.bit_length() - 1), padded_n)

# Profiler spans of ``SortEngine.sort`` (DESIGN.md §4).
SPAN_SORT = "sort_engine.sort"
SPAN_PLAN = "sort_engine.plan"
SPAN_PAD = "sort_engine.pad"
SPAN_H2D = "sort_engine.h2d"
SPAN_EXECUTE = "sort_engine.execute"
SPAN_D2H = "sort_engine.d2h"
SPAN_UNPACK = "sort_engine.unpack"
SPAN_HOST_SORT = "sort_engine.host_sort"
"""``jax.profiler.TraceAnnotation`` names, plain strings with no
``#key=value`` arguments.  ``SPAN_SORT`` covers the whole of ``sort`` on
every path; the stages nest inside it on the calling thread:
``SPAN_PLAN`` (stats, plan, fault ladder), ``SPAN_PAD`` (sim: the take
of a staging buffer from the engine's pool, the copy of the keys and the
zeroing of the tail; dist: the shard-divisibility pad), ``SPAN_H2D``, one
``SPAN_EXECUTE`` per attempt (dispatch plus the counts sync that waits
for it, so an overflow retry shows as a second span), ``SPAN_D2H`` (sim:
the device result to the caller's array, the pieces' copies and their
join included), ``SPAN_UNPACK`` (dist) and ``SPAN_HOST_SORT`` (host
path).  They record only while a profiler runs, on its clock.  No span
adds a sync: each measures what the host spends in its stage, and the
trace's device lines show the rest.  Every executable the engine builds has a stable module
name (``jit_sim_sort``, ``jit_row_sort``, ``jit_pairs_sort``,
``jit_sim_topk``, ``jit_dist_sort``)."""


def join_prefixes(
    parts: Sequence[np.ndarray], counts: Sequence[int], pool: Executor | None
) -> tuple[np.ndarray, int]:
    """``np.concatenate([np.asarray(p)[:c] for p, c in zip(parts, counts)])``
    into one fresh array, and the number of threads that wrote it.

    Writing fresh memory is bound by first-touch page faults, not by
    bandwidth, so with a ``pool`` every part gets a writer of its own,
    copying into its own slice of the output at once with the others
    (numpy releases the GIL for a same-dtype copy).  With one part, or no
    pool, the calling thread copies.  ``parts`` share a dtype; a part may
    be a device array, which its writer copies to the host first (jax
    releases the GIL for that copy too).
    """
    counts = [int(c) for c in counts]
    offsets = np.cumsum([0, *counts])
    out = np.empty(int(offsets[-1]), parts[0].dtype)

    def write(i: int) -> None:
        out[offsets[i] : offsets[i + 1]] = np.asarray(parts[i])[: counts[i]]

    if pool is None or len(parts) == 1:
        for i in range(len(parts)):
            write(i)
        return out, 1
    futures = [pool.submit(write, i) for i in range(len(parts))]
    for f in futures:
        f.result()
    return out, len(parts)


def x64_enabled() -> bool:
    """True when jax will preserve 64-bit dtypes end to end.

    With x64 off (the default), ``jnp.asarray`` silently downcasts
    int64/uint64/float64 keys to their 32-bit twins — so every jit path
    would sort *different values* than the caller handed in.  Dispatch
    (``choose_plan``) and the verify grid's pruning rules both consult this.
    """
    return bool(jax.config.jax_enable_x64)


# --------------------------------------------------------------------------
# Input statistics
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class InputStats:
    """Cheap sampled statistics of one sort request."""

    n: int
    dtype: str
    sample_size: int
    sortedness: float  # +1 ascending … −1 descending, ties neutral
    skew: float  # max/mean of the equal-width histogram (1.0 = balanced)
    dup_top_frac: float  # mass of the most frequent sampled value
    f_max_paper: float  # measured max bucket fraction, equal-width rule
    f_max_sampled: float  # measured max bucket fraction, sampled splitters
    num_buckets: int  # histogram granularity the f_max fields used

    @property
    def label(self) -> str:
        """Best-guess class in the paper's §5 taxonomy (+ 'dupes')."""
        if self.sortedness > 0.8:
            return "sorted"
        if self.sortedness < -0.8:
            return "reversed"
        if self.dup_top_frac > 0.25:
            return "dupes"
        if self.skew > 4.0:
            return "local"
        return "random"

    @property
    def skewed(self) -> bool:
        """True when equal-width ranges would overload some processor."""
        return self.skew > 2.0 or self.dup_top_frac > 0.25


def estimate_stats(
    x, *, num_buckets: int = 64, sample_size: int = 2048
) -> InputStats:
    """Measure ``InputStats`` from an evenly spread sample (host, O(sample)).

    Exactly ``min(n, sample_size)`` linspace-positioned elements: the sample
    spans the whole array (order statistics like sortedness stay meaningful
    on sorted inputs) and its size never halves across nearby ``n`` — a
    stable ``s`` keeps the 3σ term in :func:`autotune_capacity`, and hence
    the chosen capacity and jit-cache key, stable across a shape bucket.
    """
    x = np.asarray(x).ravel()
    n = x.size
    if n == 0:
        return InputStats(0, str(x.dtype), 0, 1.0, 1.0, 0.0, 0.0, 0.0, num_buckets)
    s = int(min(n, sample_size))
    idx = (np.arange(s, dtype=np.int64) * n) // s
    sample = x[idx].astype(np.float64)
    diffs = np.diff(sample)
    sortedness = (
        float(np.mean(diffs > 0) - np.mean(diffs < 0)) if diffs.size else 1.0
    )
    _, uniq_counts = np.unique(sample, return_counts=True)
    dup_top_frac = float(uniq_counts.max()) / s

    B = int(min(num_buckets, _MAX_STAT_BUCKETS))
    lo, hi = sample.min(), sample.max()
    width = (hi - lo) / B
    if width <= 0:
        ids = np.zeros(s, np.int64)
    else:
        ids = np.clip(((sample - lo) / width).astype(np.int64), 0, B - 1)
    counts = np.bincount(ids, minlength=B)
    f_max_paper = float(counts.max()) / s
    skew = f_max_paper * B  # max / (s/B)

    srt = np.sort(sample)
    splitters = srt[(np.arange(1, B) * s) // B]
    ids2 = np.searchsorted(splitters, sample, side="right")
    f_max_sampled = float(np.bincount(ids2, minlength=B).max()) / s

    return InputStats(
        n=n,
        dtype=str(x.dtype),
        sample_size=s,
        sortedness=sortedness,
        skew=float(skew),
        dup_top_frac=dup_top_frac,
        f_max_paper=f_max_paper,
        f_max_sampled=f_max_sampled,
        num_buckets=B,
    )


def estimate_batch_stats(
    padded: np.ndarray,
    seg_lens,
    *,
    num_buckets: int = 64,
    sample_size: int = 256,
) -> InputStats:
    """Worst-row ``InputStats`` for a packed ``(B, row_len)`` segment batch.

    One fused device call (``SortEngine.sort_segments``) must pick a single
    capacity for every row, so the quantity that matters is the *worst row's*
    max bucket fraction — a blended whole-batch histogram would wash a
    single pathological row out of the estimate and buy an overflow retry
    per flush.  Everything here is vectorized numpy over a strided
    ``(B, s)`` per-row sample (no per-row Python loop — the point of the
    segmented path):

    * per-row equal-width bucket counts via one offset ``bincount`` →
      ``f_max_paper``.  The sample is bucketed against each row's **true**
      min/max (one vectorized masked pass over the packed matrix — we paid
      for the pack already), not the sample's own range: a clustered row
      with tail outliers (the paper's "local" class) has a true range the
      sample misses, and the kernel's equal-width rule uses the true range —
      sample-range bucketing underestimates its hot bucket by >10×;
    * per-row top-duplicate mass via run lengths of the sorted sample
      (``dup_top_frac``); under sampled (quantile) splitters only
      indivisible duplicate mass can overload a bucket, so
      ``f_max_sampled = max(1/num_buckets, dup_top_frac)``;
    * ``sortedness`` is the mean over rows (label/diagnostics only — batch
      method choice keys off skew and duplicates).

    Per-row fractions are scaled by ``len/row_len`` before the worst-row
    reduction: capacity is measured in *elements* of a padded row, and a
    short row's hot bucket holds at most its own length — without the
    scaling one 1-element row (f̂ = 1.0 by definition) would size every
    batch buffer at the full row length.  Rows of length 0 are masked out
    of every reduction.
    """
    padded = np.asarray(padded)
    lens = np.asarray(seg_lens, dtype=np.int64).ravel()
    B, row_len = padded.shape
    total = int(lens.sum())
    dtype = str(padded.dtype)
    nb = int(min(num_buckets, _MAX_STAT_BUCKETS))
    live = lens > 0
    if total == 0 or not live.any():
        return InputStats(total, dtype, 0, 1.0, 1.0, 0.0, 0.0, 0.0, nb)
    s = int(min(row_len, sample_size))
    # Strided per-row sample over each row's own valid prefix: index
    # (j·len)//s < len for every len ≥ 1, so no pad cell is ever sampled
    # from a live row.
    idx = (np.arange(s)[None, :] * lens[:, None]) // s
    samp = padded[np.arange(B)[:, None], np.clip(idx, 0, row_len - 1)]
    samp = samp.astype(np.float64)

    # True per-row range over the valid prefix (pad cells masked out): the
    # kernel's equal-width buckets use it, so the estimate must too.
    pos = np.arange(row_len)[None, :]
    valid = pos < lens[:, None]
    pf = padded.astype(np.float64)
    lo = np.where(valid, pf, np.inf).min(axis=1)
    hi = np.where(valid, pf, -np.inf).max(axis=1)
    lo = np.where(live, lo, 0.0)
    width = np.where(live, (hi - lo) / nb, 1.0)
    width = np.where(width > 0, width, 1.0)
    # clip in float BEFORE the integer cast: dead rows sample their fill
    # value (dtype max / inf), which overflows a float→int64 cast
    ids = np.clip((samp - lo[:, None]) / width[:, None], 0, nb - 1).astype(np.int64)
    counts = np.bincount(
        (ids + np.arange(B)[:, None] * nb).ravel(), minlength=B * nb
    ).reshape(B, nb)
    # elements-of-a-padded-row units: f̂_row · (len/row_len)
    row_scale = lens / float(row_len)
    f_rows = counts.max(axis=1) / s * row_scale
    f_max_paper = float(f_rows[live].max())

    srt = np.sort(samp, axis=1)
    change = np.ones((B, s), bool)
    change[:, 1:] = srt[:, 1:] != srt[:, :-1]
    run_ids = np.cumsum(change, axis=1) - 1  # < s per row
    run_counts = np.bincount(
        (run_ids + np.arange(B)[:, None] * s).ravel(), minlength=B * s
    ).reshape(B, s)
    dup_rows = run_counts.max(axis=1) / s * row_scale
    dup_top_frac = float(dup_rows[live].max())

    diffs = np.diff(samp, axis=1)
    if diffs.shape[1]:
        per_row = np.mean(diffs > 0, axis=1) - np.mean(diffs < 0, axis=1)
        sortedness = float(per_row[live].mean())
    else:
        sortedness = 1.0
    return InputStats(
        n=total,
        dtype=dtype,
        sample_size=int(live.sum()) * s,
        sortedness=sortedness,
        skew=f_max_paper * nb,
        dup_top_frac=dup_top_frac,
        f_max_paper=f_max_paper,
        f_max_sampled=max(1.0 / nb, dup_top_frac),
        num_buckets=nb,
    )


# --------------------------------------------------------------------------
# Dispatch policy (pure — DESIGN.md §4 decision table)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SortPlan:
    path: str  # 'sim' | 'host' | 'dist'
    method: str  # sim/host: 'paper'|'sampled'; dist: +'hier'|'valiant'|'sample'
    capacity: int | None  # sim only: static per-bucket buffer length
    padded_n: int | None  # sim only: pow2 shape bucket the input pads to
    reason: str
    # dist only: simulated one-way gather time over the OHHC link graph
    # (repro.net, DESIGN.md §6) for this request's size — the measured-
    # timeline comm-cost estimate attached to dispatch decisions.
    comm_sim_s: float | None = None
    # Degraded serving (DESIGN.md §11): the active FaultScenario's name, and
    # — when the degraded gather is still possible — the netsim-predicted
    # gather slowdown (degraded/healthy, barrier accounting).  A fault that
    # makes the gather impossible rewrites the whole plan onto the healthy
    # host path instead and leaves fault_slowdown None.
    fault: str | None = None
    fault_slowdown: float | None = None


def _capacity_floor(padded_n: int, num_buckets: int) -> int:
    """The balanced floor of :func:`autotune_capacity`: the capacity every
    input gets whose measured max bucket fraction stays near ``1/P``."""
    return min(partition.default_capacity(padded_n, num_buckets), padded_n)


def autotune_capacity(
    stats: InputStats,
    method: str,
    num_buckets: int,
    padded_n: int,
    *,
    margin: float = 1.25,
) -> int:
    """Bucket capacity from the *measured* overflow model.

    Target load is ``f̂·margin·padded_n`` with ``f̂`` the measured max
    bucket fraction of the sample (for ``n ≤ sample_size`` the sample is
    the whole array, so f̂ is exact; beyond that the ×1.25 margin covers
    ~2σ of binomial sampling error for any f̂ the quantization doesn't
    already absorb — and ``SortEngine.sort``'s overflow-escalation loop
    backstops the tail, so a model miss costs a retry, never correctness).
    The legacy ``2·ceil(n/P)`` heuristic is both the floor — the
    *deterministic* answer whenever the measurement stays under it, so
    balanced traffic always lands on one capacity and one compiled
    executable — and the quantization unit above it (bounds jit-cache
    cardinality at ~P/2 steps while staying within one heuristic unit of
    the measured need).
    """
    f_hat = stats.f_max_paper if method == "paper" else stats.f_max_sampled
    base = _capacity_floor(padded_n, num_buckets)
    raw = math.ceil(f_hat * margin * padded_n)
    if raw <= base:
        return base
    cap = -(-raw // base) * base  # quantize up to a multiple of the heuristic
    cap = min(cap, padded_n + (-padded_n) % 8)
    return cap


def _grow_capacity(capacity: int, padded_n: int) -> int:
    """The capacity to retry with after a bucket overflowed ``capacity``:
    ×2, rounded up to 8, at most ``padded_n``.

    A capacity of ``padded_n`` holds every key in one bucket, so it cannot
    overflow; an overflow there is a fault, not a model miss, and raises.
    """
    if capacity >= padded_n:
        raise AssertionError("overflow with capacity == padded_n")
    capacity = min(padded_n, capacity * 2)
    return capacity + (-capacity) % 8


def choose_batch_plan(
    stats: InputStats | None,
    num_buckets: int,
    padded_n: int,
    *,
    margin: float = 1.25,
    bitonic_max: int = SEGMENT_BITONIC_MAX,
) -> SortPlan:
    """Plan ONE fused ``(B, padded_n)`` sim call for a segment batch.

    The batch twin of :func:`choose_plan`'s sim row (DESIGN.md §8): a
    homogeneous-dtype batch always takes the vmapped sim path — that is the
    point of coalescing — so the decisions left are the per-row kernel and
    one shared capacity:

    * rows up to ``bitonic_max`` take the ``bitonic`` method — a direct
      sentinel-padded row sort (the vmapped XLA sort) with **no** value
      partitioning.  At serving row sizes the P-way bucket machinery (a
      stable sort by bucket id, row slices, P per-bucket sorts) costs more
      device time than sorting the row outright, needs no capacity, and is
      immune to value skew — the fused batch IS the parallelism;
    * longer rows run the paper's bucket path: ``sampled`` splitters when
      the worst row is skewed but not duplicate-dominated (quantile
      splitters cannot split one repeated value), else the equal-width
      rule, with capacity from :func:`autotune_capacity` on the worst-row
      stats — one pathological row sizes the batch buffer rather than
      overflowing it.
    """
    if padded_n <= bitonic_max:
        return SortPlan(
            "sim", "bitonic", None, padded_n,
            f"segmented bitonic rows (Lbucket={padded_n} ≤ {bitonic_max})",
        )
    if stats is None:
        raise ValueError("choose_batch_plan needs stats for the bucket path")
    method = "sampled" if (stats.skewed and stats.dup_top_frac <= 0.25) else "paper"
    cap = autotune_capacity(stats, method, num_buckets, padded_n, margin=margin)
    return SortPlan(
        "sim", method, cap, padded_n,
        f"segmented batch ({stats.label} worst row), capacity={cap}",
    )


def choose_plan(
    stats: InputStats,
    topo: OHHCTopology,
    *,
    mesh_devices: int = 1,
    mesh_axes: Sequence[str] = (),
    host_threshold: int = HOST_THRESHOLD,
    margin: float = 1.25,
) -> SortPlan:
    """Stats × topology → (path, method, capacity).  Pure and unit-testable."""
    P = topo.total_procs
    if np.dtype(stats.dtype).itemsize == 8 and not x64_enabled():
        # jnp.asarray would silently downcast 64-bit keys to 32 bits on the
        # sim and dist paths — the numpy host path is the only executor
        # that sorts the caller's actual values.
        return SortPlan(
            "host", "paper", None, None,
            f"{stats.dtype} keys without jax x64: host is the only exact path",
        )
    if mesh_devices > 1:
        if len(mesh_axes) >= 2:
            return SortPlan(
                "dist", "hier", None, None,
                "multi-axis mesh: cross the slow (optical) tier exactly once",
            )
        if abs(stats.sortedness) > 0.8:
            return SortPlan(
                "dist", "valiant", None, None,
                "pre-sorted input: two-hop routing kills direct-route send skew",
            )
        if stats.skewed:
            return SortPlan(
                "dist", "sample", None, None,
                "value skew: balanced sampled splitters",
            )
        return SortPlan(
            "dist", "paper", None, None,
            "uniform input: faithful equal-width splitters, no sample gather",
        )

    method = "sampled" if (stats.skewed and stats.dup_top_frac <= 0.25) else "paper"
    if stats.dup_top_frac > 0.25:
        # A dominant duplicate value defeats *every* splitter rule equally;
        # equal-width is cheaper, capacity autotune absorbs the hot bucket.
        method = "paper"
    # Host path: ragged buckets are exact under any splitter, so balanced
    # splitters buy nothing at wall-clock — equal-width ids are cheaper to
    # compute and total local-sort work is the same.  'sampled' only pays
    # on the sim path, where it prevents static-capacity blowup.
    if stats.n >= host_threshold:
        return SortPlan(
            "host", "paper", None, None,
            f"n={stats.n} ≥ host threshold: exact ragged buckets, no pad waste",
        )
    padded_n = partition.bucketed_length(stats.n)
    cap = autotune_capacity(stats, method, P, padded_n, margin=margin)
    # A large skewed input stays on the device only while its planned
    # (P, capacity) buffer is the one a uniform input of its size gets.
    floor = _capacity_floor(padded_n, P)
    if stats.skewed and stats.n > (1 << 16) and cap > floor:
        return SortPlan(
            "host", "paper", None, None,
            f"large skewed input: {method} capacity {cap} is above the "
            f"balanced floor {floor}, a dense (P, capacity) buffer would dwarf n",
        )
    return SortPlan(
        "sim", method, cap, padded_n,
        f"{stats.label} input on the jit path, capacity={cap}, balanced floor {floor}",
    )


# --------------------------------------------------------------------------
# jit-able padded simulated sort (the engine's compiled unit)
# --------------------------------------------------------------------------
# Typed sentinels shared with dist_sort (see partition.max_sentinel for
# why these must carry an explicit dtype).
_sim_fill = partition.max_sentinel
_sim_low = partition.min_sentinel


def _paper_ids(x_pad: jax.Array, valid: jax.Array, *, P: int) -> jax.Array:
    """Exact equal-width §3.1 bucket ids of the valid prefix (traced).

    Integer dtypes: float32 maths collapses keys above 2^24 onto shared
    bucket edges (the int64/uint32 adversarial case), skewing counts away
    from the measured capacity model.  Unsigned subtraction is exact for
    any signed span via two's-complement wraparound; width = span//P + 1
    keeps every id strictly below P.  The numpy twin is
    ``workloads.host_bucket_ids`` — the two must agree bit-for-bit, the
    contract the top-k planner's host histogram relies on.
    """
    dtype = x_pad.dtype
    fill = _sim_fill(dtype)
    lo = jnp.min(jnp.where(valid, x_pad, fill))
    hi = jnp.max(jnp.where(valid, x_pad, _sim_low(dtype)))
    if jnp.issubdtype(dtype, jnp.integer):
        u = jnp.uint64 if jnp.dtype(dtype).itemsize == 8 else jnp.uint32
        lo_u = lo.astype(u)
        width = (hi.astype(u) - lo_u) // P + 1
        ids = ((x_pad.astype(u) - lo_u) // width).astype(jnp.int32)
        return jnp.clip(ids, 0, P - 1)  # pad tail may wrap below lo
    ftype = jnp.float64 if dtype == jnp.float64 else jnp.float32
    lo_f = lo.astype(ftype)
    width = (hi.astype(ftype) - lo_f) / P
    width = jnp.where(width > 0, width, 1.0)
    return jnp.clip(
        jnp.floor((x_pad.astype(ftype) - lo_f) / width), 0, P - 1
    ).astype(jnp.int32)


def _sim_topk_padded(
    x_pad: jax.Array,
    n_valid: jax.Array,
    *,
    P: int,
    keep: int,
    capacity: int,
):
    """Partial range-partition sort: the top-k skip rule on the sim path.

    Every element is bucketed by the paper's equal-width rule, but only
    the first ``keep`` bucket rows are scattered and sorted — the
    equal-width rule orders buckets by value range, so every element of a
    bucket past the cut is ≥ every kept element and the global head of
    length ``sum(counts[:keep])`` is exact (DESIGN.md §12).  Buckets past
    the cut route to the drop row alongside the pad tail.

    Returns ``(head, counts, kept_total)``: ``kept_total`` is the
    *unclipped* kept-element count, so ``sum(counts) < kept_total`` means
    a kept bucket overflowed ``capacity`` (escalate) while
    ``kept_total < k`` (host-side check) means the cut was too early
    (widen ``keep``).
    """
    n_pad = x_pad.shape[0]
    dtype = x_pad.dtype
    fill = _sim_fill(dtype)
    pos = jnp.arange(n_pad)
    valid = pos < n_valid
    ids = _paper_ids(x_pad, valid, P=P)
    kept = valid & (ids < keep)
    kept_total = jnp.sum(kept.astype(jnp.int32))
    ids = jnp.where(kept, ids, keep)  # past-the-cut + pad tail → drop row
    buckets, counts = partition.scatter_to_buckets(
        jnp.where(kept, x_pad, fill), ids, keep + 1, capacity, fill_value=fill
    )
    buckets, counts = buckets[:keep], counts[:keep]
    buckets = jax.vmap(jnp.sort)(buckets)
    head = partition.unscatter(buckets, counts, min(n_pad, keep * capacity))
    return head, counts, kept_total


def _sim_sort_padded(
    x_pad: jax.Array,
    n_valid: jax.Array,
    *,
    P: int,
    capacity: int,
    method: str,
    sample_size: int,
):
    """Sort the valid prefix of a padded buffer on P simulated processors.

    Shapes are static (``x_pad`` is a pow2 bucket, ``capacity`` static);
    ``n_valid`` is traced, so every length in the bucket shares one
    executable.  Invalid tail elements route to an overflow row (bucket P)
    that is dropped — they never pollute counts or splitters.  Returns
    ``(out, counts)`` with the sorted valid prefix in ``out[:n_valid]``.
    """
    n_pad = x_pad.shape[0]
    dtype = x_pad.dtype
    fill = _sim_fill(dtype)
    pos = jnp.arange(n_pad)
    valid = pos < n_valid
    if method == "paper":
        ids = _paper_ids(x_pad, valid, P=P)
    elif method == "sampled":
        s = int(min(n_pad, sample_size))
        # Strided gather over the *valid* region only (dynamic indices are
        # jit/vmap-safe; float step avoids int overflow for large buckets).
        idx = jnp.clip(
            (jnp.arange(s) * (n_valid / s)).astype(jnp.int32), 0, n_valid - 1
        )
        sample = jnp.sort(x_pad[idx])
        splitters = sample[(np.arange(1, P) * s) // P]
        ids = partition.splitter_bucket_ids(x_pad, splitters)
    else:
        raise ValueError(f"unknown sim method {method!r}")
    ids = jnp.where(valid, ids, P)  # row P = drop row for the pad tail
    buckets, counts = partition.scatter_to_buckets(
        jnp.where(valid, x_pad, fill), ids, P + 1, capacity, fill_value=fill
    )
    buckets, counts = buckets[:P], counts[:P]
    buckets = jax.vmap(jnp.sort)(buckets)
    out = partition.unscatter(buckets, counts, n_pad)
    return out, counts


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------
class SortEngine:
    """Auto-dispatching, capacity-autotuning, compile-cache-warm sorter.

    Parameters
    ----------
    topo:            OHHC instance for the simulated/host paths (default 1-D
                     full, 36 processors).
    mesh/axis_names: when given (and the mesh has >1 device), large requests
                     dispatch to ``dist_sort`` over the mesh.
    host_threshold:  sizes ≥ this go to the exact numpy path (default
                     :data:`HOST_THRESHOLD`, one past :data:`SIM_MAX_N`).
    fault_scenario:  optional ``net.faults.FaultScenario`` the engine serves
                     under (DESIGN.md §11): plans re-price the gather over
                     the degraded topology (``SortPlan.fault_slowdown``) and
                     an impossible scenario rewrites plans onto the healthy
                     host path — results stay exact either way.  Switch at
                     runtime with :meth:`set_fault_scenario`.

    The sim path (``sort`` and ``top_k``) stages each input in a host
    buffer of its shape bucket, taken from a pool the engine keeps: at
    most one idle buffer per ``(padded_n, dtype)``, so one buffer per
    power-of-two bucket in use, under twice the largest bucket's bytes
    per dtype (64 MiB at the 2^24 int32 bucket).  A fresh ``np.zeros``
    of that size would be a fresh mapping, and the copy into it
    first-touch page faults.  The keys go to ``[:n]`` and zeros to the
    tail, so the executable reads the same bytes as from a fresh buffer.
    Padding on the device instead would take an input of shape ``(n,)``:
    one executable per distinct ``n``, not per bucket.

    ``sort`` on the sim path returns a fresh, writable array of exactly
    ``n`` keys.  A bucket of at least two pieces' bytes
    (:func:`d2h_pieces`: 8 MiB a piece, at most 8) comes back from its
    executable as equal pieces; each piece that holds a valid key gets a
    thread of the engine's join pool, which copies it to the host and
    writes its keys into its own slice of the result (:func:`join_prefixes`).
    A smaller bucket is one piece, copied by the calling thread, and the
    result is a read-only view of that copy.  ``last_report["d2h_pieces"]``
    counts the pieces copied.
    """

    def __init__(
        self,
        topo: OHHCTopology | None = None,
        *,
        mesh=None,
        axis_names: Sequence[str] = ("data",),
        host_threshold: int = HOST_THRESHOLD,
        sample_size: int = 2048,
        margin: float = 1.25,
        fault_scenario=None,
    ):
        self.topo = topo if topo is not None else OHHCTopology(1, "full")
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.host_threshold = int(host_threshold)
        self.sample_size = int(sample_size)
        self.margin = float(margin)
        self.fault_scenario = fault_scenario
        self._fn_cache: dict[tuple, Callable] = {}
        self._comm_sim_cache: dict[tuple, float] = {}
        # per-scenario-name degraded classification (rebuilt rounds or the
        # GatherImpossible verdict) — warm like the caches it sits next to
        self._fault_info: dict[str, dict] = {}
        self.trace_count = 0  # incremented once per actual jit trace
        self.last_report: dict | None = None
        # The join writers of the dist path's shards and the sim path's D2H
        # pieces, one per part, made on first use.
        self._join_pool: ThreadPoolExecutor | None = None
        self._join_pool_lock = threading.Lock()
        # The sim path's idle staging buffers, one per (padded_n, dtype).
        self._pad_pool: dict[tuple[int, np.dtype], np.ndarray] = {}
        self._pad_pool_lock = threading.Lock()

    # ---------------------------------------------------------------- faults
    def set_fault_scenario(self, scenario) -> None:
        """Switch the engine onto (or off, with ``None``) a degraded
        topology.  Classification is cached per scenario *name*, the jit
        cache is untouched (the sorted output is fault-independent), and
        only plan pricing/pathing changes — so flapping scenarios never
        recompile (DESIGN.md §11)."""
        self.fault_scenario = scenario

    def _fault_state(self) -> "dict | None":
        """The active scenario classified: ``None`` when healthy, else a
        dict with ``impossible`` (bool), the scenario, and either the
        rebuilt degraded rounds + faulted router (possible) or the
        :class:`~repro.net.faults.GatherImpossible` detail + offending
        node set (impossible)."""
        sc = self.fault_scenario
        if sc is None or not getattr(sc, "is_degraded", False):
            return None
        info = self._fault_info.get(sc.name)
        if info is None:
            from repro.net.faults import GatherImpossible, degraded_gather_rounds

            try:
                rounds = degraded_gather_rounds(self.topo, sc)
            except GatherImpossible as e:
                info = {
                    "impossible": True,
                    "scenario": sc,
                    "detail": str(e),
                    "nodes": tuple(sorted(e.nodes)),
                }
            else:
                info = {
                    "impossible": False,
                    "scenario": sc,
                    "rounds": rounds,
                    "router": sc.router(self.topo),
                }
            self._fault_info[sc.name] = info
        return info

    def _apply_fault(self, plan: SortPlan, *, n: int, itemsize: int) -> SortPlan:
        """The fallback ladder (DESIGN.md §11): healthy → plan unchanged;
        degraded-but-possible → same path, gather re-priced over the
        rebuilt schedule (predicted slowdown lands in the reason and, for
        dist, in ``comm_sim_s``); impossible → the plan is rewritten onto
        the healthy host path, which needs no interconnect gather."""
        info = self._fault_state()
        if info is None:
            return plan
        name = info["scenario"].name
        if info["impossible"]:
            if plan.path == "host":
                return dataclasses.replace(
                    plan,
                    fault=name,
                    reason=f"{plan.reason}; fault={name}: degraded gather "
                    "impossible, host path unaffected",
                )
            return SortPlan(
                "host", "paper", None, None,
                f"fault={name}: degraded gather impossible "
                f"({info['detail']}); falling back to the healthy host path",
                fault=name,
            )
        healthy = self._comm_price(n, itemsize, None)
        degraded = self._comm_price(n, itemsize, info)
        ratio = degraded / healthy if healthy > 0 else 1.0
        plan = dataclasses.replace(
            plan,
            fault=name,
            fault_slowdown=ratio,
            reason=f"{plan.reason}; fault={name}: predicted "
            f"×{ratio:.2f} gather slowdown",
        )
        if plan.path == "dist":
            plan = dataclasses.replace(plan, comm_sim_s=degraded)
        return plan

    # -------------------------------------------------------------- planning
    def stats(self, x) -> InputStats:
        B = min(self.topo.total_procs, _MAX_STAT_BUCKETS)
        return estimate_stats(x, num_buckets=B, sample_size=self.sample_size)

    def plan(self, x, stats: InputStats | None = None) -> SortPlan:
        stats = stats if stats is not None else self.stats(x)
        mesh_devices = int(self.mesh.devices.size) if self.mesh is not None else 1
        plan = choose_plan(
            stats,
            self.topo,
            mesh_devices=mesh_devices,
            mesh_axes=self.axis_names if self.mesh is not None else (),
            host_threshold=self.host_threshold,
            margin=self.margin,
        )
        if plan.path == "dist":
            plan = dataclasses.replace(
                plan,
                comm_sim_s=self.comm_cost_estimate(
                    stats.n, itemsize=np.dtype(stats.dtype).itemsize
                ),
            )
        return self._apply_fault(
            plan, n=stats.n, itemsize=np.dtype(stats.dtype).itemsize
        )

    def _comm_price(self, n: int, itemsize: int, fault_info: "dict | None") -> float:
        """Barrier-mode gather time for one pow2 bucket, healthy
        (``fault_info=None``) or over a rebuilt degraded schedule — one
        cache, keyed by (bucket, itemsize, scenario name)."""
        from repro.net.links import LinkModel
        from repro.net.sim import simulate_gather, simulate_schedule

        bucket = partition.bucketed_length(max(2, n))
        name = None if fault_info is None else fault_info["scenario"].name
        key = ("netsim", bucket, itemsize, name)
        t = self._comm_sim_cache.get(key)
        if t is None:
            chunk = -(-bucket // self.topo.total_procs)
            if fault_info is None:
                t = simulate_gather(
                    self.topo,
                    link_model=LinkModel(),
                    chunk_sizes=chunk,
                    itemsize=itemsize,
                    barrier=True,
                ).total_time_s
            else:
                t = simulate_schedule(
                    fault_info["rounds"],
                    self.topo,
                    link_model=LinkModel(),
                    router=fault_info["router"],
                    chunk_sizes=chunk,
                    itemsize=itemsize,
                    barrier=True,
                ).total_time_s
            self._comm_sim_cache[key] = t
        return t

    def comm_cost_estimate(self, n: int, itemsize: int = 4) -> float:
        """Simulated one-way gather time (s) for an ``n``-element request.

        Runs the ``repro.net`` event-driven simulator (DESIGN.md §6) over
        this engine's topology with even ``n/P`` chunks — the link-level
        comm-cost estimate the dist path attaches to its dispatch
        decisions.  Cached per pow2 size bucket so the estimate is as warm
        as the jit cache it sits next to.  Under an active (and possible)
        fault scenario the price is the *degraded* schedule's (DESIGN.md
        §11); an impossible scenario prices healthy — the fallback ladder
        never runs the gather there.
        """
        info = self._fault_state()
        if info is not None and info["impossible"]:
            info = None
        return self._comm_price(n, itemsize, info)

    # -------------------------------------------------------------- jit cache
    def _get_sim_fn(self, padded_n: int, capacity: int, method: str, dtype, batched: bool):
        """The jitted sim sort of one bucket: ``(out, counts)``, where the
        non-batched ``out`` is a tuple of :func:`d2h_pieces` equal pieces
        when that is more than one."""
        pieces = 1 if batched else d2h_pieces(padded_n, dtype)
        key = ("batch" if batched else "sim", padded_n, capacity, method, str(dtype), pieces)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        def row_sort(x_pad, n_valid):
            # Direct sentinel-padded row sort (segmented batch rows,
            # DESIGN.md §8): pad cells carry the dtype max, which sorts to
            # the tail, so the valid prefix is exact even when real keys
            # equal the sentinel.  Counts are the trivial per-row total —
            # this kernel cannot overflow.
            self.trace_count += 1  # runs at trace time only
            return (
                jnp.sort(x_pad),
                jnp.reshape(n_valid.astype(jnp.int32), (1,)),
            )

        def sim_sort(x_pad, n_valid):
            self.trace_count += 1  # runs at trace time only
            out, counts = _sim_sort_padded(
                x_pad,
                n_valid,
                P=self.topo.total_procs,
                capacity=capacity,
                method=method,
                sample_size=min(self.sample_size, padded_n),
            )
            if pieces > 1:  # each piece copies to the host on its own thread
                out = tuple(jnp.split(out, pieces))
            return out, counts

        traced = row_sort if method == "bitonic" else sim_sort
        fn = jax.jit(jax.vmap(traced) if batched else traced)
        self._fn_cache[key] = fn
        return fn

    def _get_dist_fn(self, shape: tuple, dtype, method: str, cf: float):
        """The jitted ``dist_sort`` over this engine's mesh, one per
        (shape, dtype, method, capacity factor)."""
        from repro.core.dist_sort import dist_sort

        key = ("dist", tuple(shape), str(np.dtype(dtype)), method, cf)
        fn = self._fn_cache.get(key)
        if fn is None:
            # update_wrapper names the module jit_dist_sort
            fn = jax.jit(functools.update_wrapper(
                functools.partial(
                    dist_sort,
                    mesh=self.mesh,
                    axis_names=self.axis_names,
                    method=method,
                    capacity_factor=cf,
                ),
                dist_sort,
            ))
            self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ sort
    def sort(self, x, *, plan: SortPlan | None = None) -> np.ndarray:
        """Globally sort ``x``; always exact (overflow escalates capacity).

        Keys must be NaN-free: like every range-partitioning sort in this
        repo, NaN poisons the min/max splitter computation (NaN also
        compares after the +inf pad fill, so such elements can vanish from
        the valid prefix).  Pre-filter NaNs before sorting float keys.

        Under ``jax.profiler`` the call and its stages show as the
        :data:`SPAN_SORT` spans.
        """
        with jax.profiler.TraceAnnotation(SPAN_SORT):
            x_np = np.asarray(x).ravel()
            n = x_np.size
            if n <= 1:
                self.last_report = {"plan": None, "n": n, "overflow_retries": 0}
                return x_np.copy()
            # Stats are only measured when something consumes them: planning
            # (no explicit plan) or the dist path's capacity factor.  A forced
            # sim/host plan skips the sample entirely.
            stats = None
            with jax.profiler.TraceAnnotation(SPAN_PLAN):
                if plan is None:
                    stats = self.stats(x_np)
                    plan = self.plan(x_np, stats)  # fault ladder applied inside
                else:
                    # Forced plans go through the same ladder: an impossible
                    # scenario rewrites even an explicit sim/dist plan onto the
                    # healthy host path — that override IS the degraded-serving
                    # contract (zero wrong answers, DESIGN.md §11).
                    plan = self._apply_fault(plan, n=n, itemsize=x_np.dtype.itemsize)
            if plan.path == "host":
                with jax.profiler.TraceAnnotation(SPAN_HOST_SORT):
                    r = ohhc_sort_host(x_np, self.topo, method=plan.method)
                self.last_report = {
                    "plan": plan, "n": n, "stats": stats, "overflow_retries": 0,
                    "counts_sum": int(r.bucket_sizes.sum()),
                    "counts": np.asarray(r.bucket_sizes),
                }
                return r.sorted_array
            if plan.path == "dist":
                return self._sort_dist(x_np, plan, stats)
            return self._sort_sim(x_np, plan, stats)

    def _sort_sim(self, x_np: np.ndarray, plan: SortPlan, stats) -> np.ndarray:
        n = x_np.size
        padded_n = plan.padded_n or partition.bucketed_length(n)
        capacity = plan.capacity or partition.default_capacity(padded_n, self.topo.total_procs)
        with jax.profiler.TraceAnnotation(SPAN_PAD):
            x_pad, reused = self._stage_pad(x_np, padded_n)
        with jax.profiler.TraceAnnotation(SPAN_H2D):
            xj = jnp.asarray(x_pad)
        retries = 0
        while True:
            with jax.profiler.TraceAnnotation(SPAN_EXECUTE):
                fn = self._get_sim_fn(padded_n, capacity, plan.method, x_np.dtype, False)
                out, counts = fn(xj, n)
                got = int(jnp.sum(counts))
            if got == n:
                break
            # Measured-model miss: escalate capacity and re-run.
            capacity = _grow_capacity(capacity, padded_n)
            retries += 1
        with jax.profiler.TraceAnnotation(SPAN_D2H):
            if isinstance(out, tuple):
                # Pieces past n hold no valid key and are not copied.
                size = out[0].size
                live = -(-n // size)
                sizes = [size] * (live - 1) + [n - (live - 1) * size]
                out, copied = join_prefixes(out[:live], sizes, self._join_writers(live))
            else:
                out, copied = np.asarray(out)[:n], 1
            counts = np.asarray(counts)
        self._release_pad(x_pad)
        self.last_report = {
            "plan": plan, "n": n, "stats": stats, "capacity_used": capacity,
            "counts_sum": got, "overflow_retries": retries, "counts": counts,
            "pad_reused": reused, "d2h_pieces": copied,
        }
        return out

    def _stage_pad(self, x_np: np.ndarray, padded_n: int) -> tuple[np.ndarray, bool]:
        """``x_np`` padded with zeros to ``padded_n``, in a buffer taken from
        the pool, and whether the pool had one (else it is new).  The caller
        owns the buffer until it hands it back with :meth:`_release_pad`,
        once the executable that reads it has finished; a caller that
        raises first never hands it back, and the buffer is dropped."""
        with self._pad_pool_lock:
            x_pad = self._pad_pool.pop((padded_n, x_np.dtype), None)
        reused = x_pad is not None
        if not reused:
            x_pad = np.empty(padded_n, x_np.dtype)
        n = x_np.size
        x_pad[:n] = x_np
        x_pad[n:] = 0
        return x_pad, reused

    def _release_pad(self, x_pad: np.ndarray) -> None:
        """Return a staging buffer to the pool, unless it already holds an
        idle one of that shape and dtype."""
        with self._pad_pool_lock:
            self._pad_pool.setdefault((x_pad.size, x_pad.dtype), x_pad)

    # --------------------------------------------------------------- batched
    def plan_segments(self, keys, seg_lens) -> SortPlan:
        """Batch plan (method + shared capacity) for ``sort_segments`` traffic.

        Packs, measures worst-row stats (``estimate_batch_stats``) and runs
        the batch policy (``choose_batch_plan``) without executing the sort —
        the introspection hook the sortd service and benchmarks use.
        """
        keys = np.asarray(keys).ravel()
        lens = np.asarray(seg_lens, dtype=np.int64).ravel()
        padded_n = partition.bucketed_length(int(lens.max()) if lens.size else 1)
        stats = None
        if padded_n > SEGMENT_BITONIC_MAX:
            padded = partition.pack_segments(keys, lens, padded_n)
            stats = estimate_batch_stats(
                padded, lens,
                num_buckets=min(self.topo.total_procs, _MAX_STAT_BUCKETS),
            )
        return choose_batch_plan(
            stats, self.topo.total_procs, padded_n, margin=self.margin
        )

    def sort_segments(
        self, keys, seg_lens, *, plan: SortPlan | None = None,
        return_padded: bool = False,
    ):
        """Sort ``B`` variable-length segments in ONE padded device call.

        ``keys`` is the flat concatenation of the segments and ``seg_lens``
        their lengths — the fused serving primitive (DESIGN.md §8): the whole
        batch packs into one ``(B, Lbucket)`` sentinel-padded matrix
        (``partition.pack_segments``, ``Lbucket`` the pow2 shape bucket of the
        longest segment), batch stats and capacity come from one vectorized
        worst-row measurement (no per-row Python loop), and a single vmapped
        executable from the warm jit cache sorts every row.  Both traced
        axes are shape-bucketed: rows pad to the pow2 ``Lbucket`` and the
        batch axis pads to a pow2 with zero-length phantom rows, so a
        serving stream of arbitrary (B, length) mixes reuses a handful of
        executables.  Overflow escalates capacity ×2 exactly like ``sort``,
        so results are always exact.

        Returns a list of sorted numpy segments; with ``return_padded=True``
        the raw device-resident ``(B, Lbucket)`` output instead (row ``i``'s
        sorted segment is ``out[i, :seg_lens[i]]``) — nothing but the tiny
        per-row counts check crosses back to the host, so pipelines can keep
        chaining device work without a payload sync.

        64-bit keys without jax x64 have no exact jit path (``choose_plan``'s
        host rule); they fall back to an exact per-segment host sort and
        cannot honor ``return_padded``.
        """
        keys = np.asarray(keys).ravel()
        lens = np.asarray(seg_lens, dtype=np.int64).ravel()
        if (lens < 0).any():
            raise ValueError("sort_segments: negative segment length")
        if int(lens.sum()) != keys.size:
            raise ValueError(
                f"sort_segments: seg_lens sum to {int(lens.sum())} "
                f"but keys has {keys.size} elements"
            )
        B = int(lens.size)
        total = keys.size
        max_n = int(lens.max()) if B else 0
        if keys.dtype.itemsize == 8 and not x64_enabled():
            return self._sort_segments_on_host(
                keys, lens, return_padded,
                "64-bit keys without x64 only have the exact host fallback",
                SortPlan(
                    "host", "paper", None, None,
                    f"{keys.dtype} segments without jax x64: exact host fallback",
                ),
            )
        fault_info = self._fault_state()
        if fault_info is not None and fault_info["impossible"]:
            # An impossible scenario has no degraded gather to run, so serve
            # the batch exactly on the healthy host path (DESIGN.md §11).
            name = fault_info["scenario"].name
            return self._sort_segments_on_host(
                keys, lens, return_padded,
                f"fault scenario {name!r} makes the degraded gather "
                "impossible and forces the host fallback",
                SortPlan(
                    "host", "paper", None, None,
                    f"fault={name}: degraded gather impossible "
                    f"({fault_info['detail']}); exact host fallback",
                    fault=name,
                ),
            )
        padded_n = partition.bucketed_length(max(max_n, 1))
        if B == 0 or max_n <= 1:
            # Nothing to sort row-wise; keep the trivial case off the device.
            self.last_report = {
                "plan": SortPlan("sim", "paper", None, padded_n, "trivial batch"),
                "n": total, "batch": B, "overflow_retries": 0,
            }
            if return_padded:
                return jnp.asarray(partition.pack_segments(keys, lens, padded_n))
            return partition.unpack_segments(
                partition.pack_segments(keys, lens, padded_n), lens
            )
        # The batch axis is part of the traced shape: without bucketing it,
        # every distinct flush size B would compile its own executable (a
        # ~seconds stall per size on this container).  Pad B up to a pow2
        # with zero-length phantom rows — they carry no valid elements, so
        # stats, capacity and counts ignore them; worst-case extra row work
        # is bounded at 2× and the executable count at log2(max_batch).
        # Serving-size (bitonic) rows get a floor of 8 — phantom rows are
        # cheap there and the floor collapses the smallest batch sizes onto
        # one executable; bucket-path rows are expensive enough that a
        # phantom row floor would dominate a small batch's device time.
        b_floor = 3 if padded_n <= SEGMENT_BITONIC_MAX else 0
        B_pad = 1 << max(int(B - 1).bit_length(), b_floor)
        lens_pad = np.zeros(B_pad, np.int64)
        lens_pad[:B] = lens
        padded = partition.pack_segments(keys, lens_pad, padded_n)
        stats = None
        if plan is None:
            if padded_n > SEGMENT_BITONIC_MAX:
                # bitonic rows need no capacity, so only bucket rows pay
                # for the stats pass
                stats = estimate_batch_stats(
                    padded, lens_pad,
                    num_buckets=min(self.topo.total_procs, _MAX_STAT_BUCKETS),
                )
            plan = choose_batch_plan(
                stats, self.topo.total_procs, padded_n, margin=self.margin
            )
        # Degraded-but-possible scenario: same fused sim path, plan
        # annotated with the predicted gather slowdown (impossible was
        # already rerouted to the host fallback above).
        plan = self._apply_fault(plan, n=max(total, 1), itemsize=keys.dtype.itemsize)
        if plan.path != "sim":
            raise ValueError(f"sort_segments only runs the sim path, got {plan.path!r}")
        method = plan.method
        capacity = 0 if method == "bitonic" else (
            plan.capacity
            or partition.default_capacity(padded_n, self.topo.total_procs)
        )
        xj = jnp.asarray(padded)
        nsj = jnp.asarray(lens_pad.astype(np.int32))
        retries = 0
        while True:
            fn = self._get_sim_fn(padded_n, capacity, method, keys.dtype, True)
            out, counts = fn(xj, nsj)
            per_row = np.asarray(jnp.sum(counts, axis=-1))
            if np.array_equal(per_row, lens_pad):
                break
            capacity = _grow_capacity(capacity, padded_n)
            retries += 1
        self.last_report = {
            "plan": dataclasses.replace(
                plan, capacity=capacity if method != "bitonic" else None
            ),
            "n": total, "stats": stats, "batch": B, "batch_padded": B_pad,
            "overflow_retries": retries,
            "pad_cells": B * padded_n - total,  # pad-waste the metrics layer reports
        }
        if return_padded:
            return out[:B]
        return partition.unpack_segments(np.asarray(out)[:B], lens)

    def _sort_segments_on_host(
        self, keys: np.ndarray, lens: np.ndarray, return_padded: bool,
        why: str, plan: SortPlan,
    ) -> list[np.ndarray]:
        """``sort_segments``' exact host fallback: one ``np.sort`` per
        segment.  It has no device output, so ``return_padded`` raises
        with ``why``; ``plan`` is what ``last_report`` records."""
        if return_padded:
            raise ValueError(f"return_padded needs the jit path; {why}")
        segs = np.split(keys, np.cumsum(lens)[:-1]) if lens.size else []
        self.last_report = {
            "plan": plan, "n": keys.size, "batch": int(lens.size),
            "overflow_retries": 0,
        }
        return [np.sort(seg) for seg in segs]

    def sort_many(self, xs: Sequence) -> list[np.ndarray]:
        """Sort a batch of arrays with ONE vmapped executable.

        Thin wrapper over ``sort_segments``: concatenates the batch into the
        flat segmented form and fuses it into a single padded device call —
        the pre-sortd per-array stats/dispatch loop is gone (DESIGN.md §8).
        """
        arrs = [np.asarray(a).ravel() for a in xs]
        if not arrs:
            return []
        dtype = arrs[0].dtype
        if any(a.dtype != dtype for a in arrs):
            raise ValueError("sort_many requires a homogeneous dtype batch")
        lens = [a.size for a in arrs]
        if max(lens) <= 1:
            return [a.copy() for a in arrs]
        flat = np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
        return self.sort_segments(flat, lens)

    def sort_pairs(self, keys, vals):
        """Key/payload sort — flat arrays on one multi-operand XLA sort,
        pytrees via a permutation gather (DESIGN.md §12).

        A single flat 1-D payload array is sorted by ``lax.sort`` over
        (key, validity tag, payload) directly (warm shape cache, returns
        jax arrays) — the serving hot path (length-ordering a request
        batch) calls this with a different batch size every tick, and pow2
        bucketing makes all of them share a handful of executables instead
        of one per size.

        Any other payload pytree (nested dicts/tuples, mixed dtypes,
        multi-dim leaves) rides :meth:`argsort_keys`: the same flat pairs
        sort orders ``(key, index)`` once, then every flattened leaf is
        gathered by the permutation on the host — byte-exact for every
        leaf dtype (64-bit leaves survive without jax x64).  Returns
        ``(sorted_keys, same-structure payload)`` as numpy.
        """
        leaves, treedef = jax.tree_util.tree_flatten(vals)
        if (
            len(leaves) == 1
            and treedef == jax.tree_util.tree_structure(0)
            and np.ndim(leaves[0]) == 1
        ):
            return self._sort_pairs_flat(keys, leaves[0])
        return self._sort_pairs_tree(keys, leaves, treedef)

    def _get_pairs_fn(self, n_pad: int, key_dtype, val_dtype):
        key = ("pairs", n_pad, str(key_dtype), str(val_dtype))
        fn = self._fn_cache.get(key)
        if fn is None:
            def pairs_sort(k, v, n_valid):
                self.trace_count += 1  # runs at trace time only
                # Lexicographic (key, validity tag) order: pad slots tag 1,
                # so real keys equal to the dtype-max pad sentinel keep
                # their payloads ahead of the zero-payload pad tail (the
                # sentinel-tie hazard).  n_valid is traced, so every length
                # in the bucket shares one executable.
                tags = (jnp.arange(n_pad) >= n_valid).astype(jnp.int32)
                ks, _, vs = jax.lax.sort((k, tags, v), num_keys=2)
                return ks, vs

            fn = jax.jit(pairs_sort)
            self._fn_cache[key] = fn
        return fn

    def _sort_pairs_flat(self, keys, vals):
        """The flat path: one payload array through a multi-operand XLA
        sort (sentinel-tie safe, n_valid traced)."""
        keys = jnp.asarray(keys).ravel()
        vals = jnp.asarray(vals).ravel()
        n = keys.shape[0]
        if n <= 1:
            return keys, vals
        n_pad = partition.bucketed_length(n)
        fn = self._get_pairs_fn(n_pad, keys.dtype, vals.dtype)
        fill = _sim_fill(keys.dtype)
        kp = jnp.concatenate([keys, jnp.full((n_pad - n,), fill, keys.dtype)])
        vp = jnp.concatenate([vals, jnp.zeros((n_pad - n,), vals.dtype)])
        ks, vs = fn(kp, vp, n)
        self.last_report = {
            "plan": SortPlan(
                "sim", "pairs", None, n_pad,
                f"pairs: lax.sort over (key, validity tag, payload), n={n}",
            ),
            "n": n, "overflow_retries": 0, "counts_sum": n,
        }
        return ks[:n], vs[:n]

    def argsort_keys(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """``(sorted_keys, permutation)`` with ``sorted_keys == keys[perm]``.

        The permutation comes from the flat pairs sort of
        ``(key, arange)`` — the sentinel-tie-safe path, so keys equal to
        the dtype max keep their payload.  64-bit keys without jax x64
        take the host stable argsort (the same exactness rule as
        ``choose_plan``'s host fallback).
        """
        keys_np = np.asarray(keys).ravel()
        n = keys_np.size
        if n <= 1:
            return keys_np.copy(), np.arange(n, dtype=np.int64)
        if keys_np.dtype.itemsize == 8 and not x64_enabled():
            perm = np.argsort(keys_np, kind="stable")
            self.last_report = {
                "plan": SortPlan(
                    "host", "pairs", None, None,
                    f"argsort: {keys_np.dtype} n={n} host stable argsort "
                    "(x64 exactness rule)",
                ),
                "n": n, "overflow_retries": 0, "counts_sum": n,
            }
            return keys_np[perm], perm
        ks, perm = self._sort_pairs_flat(keys_np, np.arange(n, dtype=np.int32))
        self.last_report = {
            "plan": SortPlan(
                "sim", "pairs", None, partition.bucketed_length(n),
                f"argsort: lax.sort over (key, validity tag, arange), n={n}",
            ),
            "n": n, "overflow_retries": 0, "counts_sum": n,
        }
        return np.asarray(ks), np.asarray(perm).astype(np.int64)

    def _sort_pairs_tree(self, keys, leaves, treedef):
        """Pytree payload path: one key argsort, then a host gather of
        every flattened leaf along its leading axis (byte-exact)."""
        keys_np = np.asarray(keys).ravel()
        n = keys_np.size
        np_leaves = [np.asarray(leaf) for leaf in leaves]
        for i, leaf in enumerate(np_leaves):
            if leaf.ndim < 1 or leaf.shape[0] != n:
                raise ValueError(
                    f"sort_pairs: payload leaf {i} has shape {leaf.shape}; "
                    f"leading dim must equal n={n}"
                )
        if n <= 1:
            out_leaves = [leaf.copy() for leaf in np_leaves]
            return keys_np.copy(), jax.tree_util.tree_unflatten(
                treedef, out_leaves
            )
        ks, perm = self.argsort_keys(keys_np)
        out_leaves = [leaf[perm] for leaf in np_leaves]
        return ks, jax.tree_util.tree_unflatten(treedef, out_leaves)

    # ----------------------------------------------------------------- top-k
    def _check_top_k(self, n: int, k) -> int:
        if isinstance(k, bool) or not isinstance(k, (int, np.integer)):
            raise TypeError(f"top_k: k must be an int, got {type(k).__name__}")
        k = int(k)
        if k < 0:
            raise ValueError(f"top_k: k must be >= 0, got {k}")
        if k > n:
            raise TopKTooLarge(f"top_k: k={k} exceeds n={n}")
        return k

    def _plan_top_k_info(self, x_np: np.ndarray, k: int):
        """Plan + exact skip/capacity accounting for one top-k request.

        One O(n) host histogram under the *exact* kernel bucket rule
        (``workloads.host_bucket_ids``) yields the cut bucket, the
        skipped-bucket count, and — the satellite fix — a capacity sized
        to the KEPT buckets only: a full sort's ``autotune_capacity`` is
        worst-bucket-sized over the whole array, and a top-k plan must not
        inherit a capacity paid for buckets it skips.
        """
        n = x_np.size
        P = self.topo.total_procs
        ids = workloads.host_bucket_ids(x_np, P)
        counts = np.bincount(ids, minlength=P)
        keep, skipped = workloads.topk_cut(counts, k)
        kept_count = int(counts[:keep].sum())
        # Static-shape quantization for the jit cache: the executed kept
        # prefix is the pow2 ceiling of the exact cut (capped at P), so
        # nearby cuts share one executable.
        keep_exec = min(P, 1 << int(keep - 1).bit_length())
        padded_n = partition.bucketed_length(n)
        if (
            (x_np.dtype.itemsize == 8 and not x64_enabled())
            or n >= self.host_threshold
            or kept_count <= n // 4
        ):
            # Small heads (or no exact jit path): the host executor sorts
            # only the kept prefix — numpy on n/4 elements beats a padded
            # device round-trip of the whole array.
            plan = SortPlan(
                "host", "topk", None, None,
                f"top_k k={k}: skipped={skipped}/{P} buckets past the cut, "
                f"kept {kept_count}/{n} keys; exact host head",
            )
        else:
            cap = max(int(counts[:keep_exec].max()), 8)
            cap += (-cap) % 8
            cap = min(cap, padded_n + (-padded_n) % 8)
            plan = SortPlan(
                "sim", "topk", cap, padded_n,
                f"top_k k={k}: skipped={P - keep_exec}/{P} buckets past the "
                f"cut (exact cut {keep}, pow2 exec {keep_exec}), kept-bucket "
                f"capacity={cap}",
            )
        plan = self._apply_fault(plan, n=n, itemsize=x_np.dtype.itemsize)
        info = {
            "keep": keep,
            "keep_exec": keep_exec,
            "skipped": skipped,
            "kept_count": kept_count,
            "counts": counts,
        }
        return plan, info

    def plan_top_k(self, x, k) -> SortPlan:
        """The top-k dispatch decision without executing it — the
        introspection twin of :meth:`plan` for the head workload."""
        x_np = np.asarray(x).ravel()
        k = self._check_top_k(x_np.size, k)
        if k == 0 or x_np.size <= 1:
            return SortPlan(
                "host", "topk", None, None, f"top_k k={k}: trivial head"
            )
        return self._plan_top_k_info(x_np, k)[0]

    def top_k(self, x, k, *, plan: SortPlan | None = None) -> np.ndarray:
        """The sorted head ``np.sort(x)[:k]`` without sorting past rank k.

        Reuses the partition kernel's bucket machinery: the equal-width
        rule orders buckets by value range, so once the cumulative bucket
        histogram covers ``k`` every later bucket is wholly past the head
        and is skipped (``SortPlan.reason`` reports the skipped-bucket
        count).  Always exact, ties at rank k included — the head is a
        prefix of the true sorted order.  ``k > n`` raises
        :class:`~repro.core.workloads.TopKTooLarge`.
        """
        x_np = np.asarray(x).ravel()
        n = x_np.size
        k = self._check_top_k(n, k)
        P = self.topo.total_procs
        if k == 0 or n == 0:
            self.last_report = {
                "plan": None, "n": n, "k": k, "overflow_retries": 0,
                "skipped_buckets": P, "kept_count": 0,
            }
            return x_np[:0].copy()
        if n <= 1:
            self.last_report = {
                "plan": None, "n": n, "k": k, "overflow_retries": 0,
                "skipped_buckets": 0, "kept_count": n,
            }
            return x_np.copy()
        auto_plan, info = self._plan_top_k_info(x_np, k)
        if plan is None:
            plan = auto_plan
        else:
            plan = self._apply_fault(plan, n=n, itemsize=x_np.dtype.itemsize)
        if plan.path != "sim":
            head, hinfo = workloads.host_top_k(x_np, k, P)
            self.last_report = {
                "plan": plan, "n": n, "k": k, "overflow_retries": 0,
                "skipped_buckets": hinfo["skipped_buckets"],
                "kept_count": hinfo["kept_count"],
                "counts_sum": hinfo["kept_count"],
            }
            return head
        padded_n = plan.padded_n or partition.bucketed_length(n)
        capacity = plan.capacity or partition.default_capacity(padded_n, P)
        keep = info["keep_exec"]
        x_pad, reused = self._stage_pad(x_np, padded_n)
        xj = jnp.asarray(x_pad)
        retries = 0
        while True:
            fn = self._get_topk_fn(padded_n, capacity, keep, x_np.dtype)
            head_pad, counts, kept_total = fn(xj, n)
            kept_total = int(kept_total)
            got = int(jnp.sum(counts))
            if got < kept_total:
                # A kept bucket overflowed its (kept-only) capacity:
                # escalate exactly like sort's retry loop.
                capacity = _grow_capacity(capacity, padded_n)
                retries += 1
                continue
            if kept_total < k:
                # A forced/stale plan cut too early: widen the kept prefix.
                if keep >= P:
                    raise AssertionError("top_k cut miss with keep == P")
                keep = min(P, keep * 2)
                retries += 1
                continue
            break
        head = np.asarray(head_pad)[:k]
        self._release_pad(x_pad)
        self.last_report = {
            "plan": plan, "n": n, "k": k, "capacity_used": capacity,
            "skipped_buckets": P - keep, "kept_count": kept_total,
            "counts_sum": got, "overflow_retries": retries,
            "counts": np.asarray(counts), "pad_reused": reused,
        }
        return head

    def _get_topk_fn(self, padded_n: int, capacity: int, keep: int, dtype):
        key = ("topk", padded_n, capacity, keep, str(dtype))
        fn = self._fn_cache.get(key)
        if fn is None:
            def sim_topk(x_pad, n_valid):
                self.trace_count += 1  # runs at trace time only
                return _sim_topk_padded(
                    x_pad, n_valid, P=self.topo.total_procs, keep=keep,
                    capacity=capacity,
                )

            fn = jax.jit(sim_topk)
            self._fn_cache[key] = fn
        return fn

    # ----------------------------------------------------------------- merge
    def merge_sorted(self, sorted_buf, new_keys) -> np.ndarray:
        """Fold ``new_keys`` into an already-sorted buffer incrementally.

        The streaming workload (DESIGN.md §12): a buffer that grows every
        tick no longer pays O(n log n) per tick — the increment goes
        through the full engine dispatch (``sort``) and the two ascending
        runs fuse in O(n + m) with the ``searchsorted`` gather, the
        paper's merge-free accumulation applied across time.  The buffer
        must already be ascending (validated, O(n)); dtype mismatches are
        a typed error, never a silent cast.
        """
        buf = np.asarray(sorted_buf).ravel()
        new = np.asarray(new_keys).ravel()
        if buf.dtype != new.dtype:
            raise ValueError(
                f"merge_sorted: dtype mismatch — buffer {buf.dtype} "
                f"vs new keys {new.dtype}"
            )
        if not workloads.check_sorted(buf):
            raise ValueError(
                "merge_sorted: sorted_buf is not ascending — sort it first"
            )
        if new.size == 0:
            self.last_report = {
                "plan": SortPlan(
                    "host", "merge", None, None,
                    f"merge: empty increment onto |buf|={buf.size}",
                ),
                "n": buf.size, "overflow_retries": 0,
                "counts_sum": buf.size, "merged_new": 0,
            }
            return buf.copy()
        inner_plan = None
        retries = 0
        if new.size > 1:
            new_sorted = self.sort(new)  # full dispatch for the increment
            inner = self.last_report or {}
            inner_plan = inner.get("plan")
            retries = int(inner.get("overflow_retries", 0))
        else:
            new_sorted = new
        out = workloads.merge_sorted_arrays(buf, new_sorted)
        plan = SortPlan(
            "host", "merge", None, None,
            f"merge: |buf|={buf.size} reused sorted, |new|={new.size} "
            f"engine-sorted ({getattr(inner_plan, 'path', 'trivial')}"
            f"/{getattr(inner_plan, 'method', '-')}), "
            "O(n+m) searchsorted gather",
        )
        self.last_report = {
            "plan": plan, "n": out.size, "overflow_retries": retries,
            "counts_sum": out.size, "merged_new": int(new.size),
            "inner_plan": inner_plan,
        }
        return out

    # ------------------------------------------------------------------ dist
    def _sort_dist(self, x_np: np.ndarray, plan: SortPlan, stats) -> np.ndarray:
        from repro.core.dist_sort import row_capacity

        if stats is None:
            with jax.profiler.TraceAnnotation(SPAN_PLAN):
                stats = self.stats(x_np)
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        num_shards = 1
        for ax in self.axis_names:
            num_shards *= sizes[ax]
        n = x_np.size
        pad = (-n) % num_shards
        with jax.profiler.TraceAnnotation(SPAN_PAD):
            if pad:
                fill = (
                    np.iinfo(x_np.dtype).max
                    if np.issubdtype(x_np.dtype, np.integer)
                    else np.inf
                )
                x_np = np.concatenate([x_np, np.full(pad, fill, x_np.dtype)])
        f_hat = stats.f_max_sampled if plan.method != "paper" else stats.f_max_paper
        cf = max(2.0, self.margin * f_hat * num_shards * 2.0)
        # Each device receives only its own shard (never the whole array on
        # device 0).
        with jax.profiler.TraceAnnotation(SPAN_H2D):
            xj = jax.device_put(
                x_np, NamedSharding(self.mesh, PartitionSpec(self.axis_names))
            )
        retries = 0
        while True:
            with jax.profiler.TraceAnnotation(SPAN_EXECUTE):
                fn = self._get_dist_fn(x_np.shape, x_np.dtype, plan.method, cf)
                vals, counts = fn(xj)
                counts = np.asarray(counts).ravel()
            if int(counts.sum()) == x_np.size:
                break
            # Overflow drops elements (dist_sort contract); escalate like
            # the sim path.  cf == num_shards cannot overflow: every dest
            # row then holds a sender's whole shard.
            if cf >= num_shards:
                raise AssertionError("dist overflow at capacity_factor == shards")
            cf = min(float(num_shards), cf * 2.0)
            retries += 1
        with jax.profiler.TraceAnnotation(SPAN_D2H):
            # One host copy per shard, all started at once: np.asarray of
            # the global array would copy every slot into a second buffer.
            by_start = {s.index[0].start or 0: s.data for s in vals.addressable_shards}
            shards = [by_start[start] for start in sorted(by_start)]
            for sh in shards:
                sh.copy_to_host_async()
            shards = [np.asarray(sh) for sh in shards]
        with jax.profiler.TraceAnnotation(SPAN_UNPACK):
            out, writers = join_prefixes(shards, counts, self._join_writers(len(shards)))
        self.last_report = {
            "plan": plan, "n": n, "stats": stats,
            # counts includes the shard-divisibility pad (max-sentinel
            # elements that sort to the tail and are sliced off below);
            # report caller elements so conservation means counts_sum == n.
            "counts_sum": int(counts.sum()) - pad, "overflow_retries": retries,
            # The caller's keys each shard received: the pad is the tail of
            # the shards' concatenation.
            "shard_counts": np.diff(np.minimum(np.cumsum(counts), n), prepend=0).tolist(),
            # Slots per (source, destination) row of the attempt that
            # succeeded; hier sizes its two stages' rows otherwise.
            "dist_capacity": (
                None if plan.method == "hier"
                else row_capacity(x_np.size, num_shards, cf)
            ),
            "comm_sim_s": (
                plan.comm_sim_s
                if plan.comm_sim_s is not None
                else self.comm_cost_estimate(n, itemsize=x_np.dtype.itemsize)
            ),
            "unpack_writers": writers,
        }
        return out[:n]

    def _join_writers(self, num_parts: int) -> ThreadPoolExecutor | None:
        """The pool that joins ``num_parts`` parts on the host (the dist
        path's shards, the sim path's D2H pieces), shared by both paths and
        by concurrent callers; ``None`` for one part.  It is as wide as the
        larger of the two paths' needs, whichever makes it: one worker per
        device of the mesh, or :data:`D2H_MAX_PIECES`."""
        if num_parts == 1:
            return None
        with self._join_pool_lock:
            if self._join_pool is None:
                # Imported here: a process that never joins parts never loads it.
                from concurrent.futures import ThreadPoolExecutor

                devices = int(self.mesh.devices.size) if self.mesh is not None else 1
                self._join_pool = ThreadPoolExecutor(
                    max(D2H_MAX_PIECES, devices), thread_name_prefix="sort_join"
                )
            return self._join_pool
