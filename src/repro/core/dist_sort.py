"""Distributed sort over a real JAX device mesh (``shard_map``).

Public API
----------
``dist_sort(x, mesh=..., axis_names=..., method=...)`` — globally sort a
sharded array.  Output contract (the TPU-native adaptation of the paper's
"array gathered at the master", DESIGN.md §2): the result stays sharded,
padded per shard with +inf/int-max, with per-shard valid counts; shard *i*
holds only keys ≤ every key of shard *i+1*, so the concatenation of valid
prefixes in shard order is the sorted array.

Methods
-------
* ``'sample'``  — balanced splitters + one fused ``all_to_all`` (the
  beyond-paper production path).
* ``'paper'``   — §3.1 equal-width range splitters + the same fused
  exchange (isolates the paper's splitter rule from its hop-by-hop
  transport so benchmarks can attribute cost).
* ``'hier'``    — two-level exchange for multi-pod meshes: one
  ``all_to_all`` *inside* each pod, then exactly one exchange *across*
  pods — the paper's "cross the optical tier once" schedule mapped onto
  mesh axes (electrical links = intra-pod axes, optical = pod axis).
* ``'valiant'`` — two-hop load-balanced routing: a deterministic
  round-robin interleave first (every device ends up with a stratified
  sample of the whole array), then the normal splitter exchange.  Kills
  the worst-case send skew of pre-sorted inputs (where shard i's whole
  payload targets device i): per-(src,dst) traffic becomes uniform, so
  ``capacity_factor≈2`` suffices where the direct route needs ≈P.
  Costs one extra all_to_all — the classic Valiant bandwidth/worst-case
  trade, and this framework's straggler-mitigation story for the sort.

All paths are jit-compatible: bucket buffers have static ``capacity``;
overflow (never hit with sampled splitters at the default factor) drops
elements and is surfaced via the returned counts, which tests check.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import partition

__all__ = ["dist_sort", "host_check_globally_sorted", "row_capacity"]

# Typed scalar (a bare Python int would be weak-typed int32 and overflow
# for uint32 where it feeds jnp.where directly).
_fill_value = partition.max_sentinel


def _local_splitters(local: jax.Array, num_shards: int, axis_names, oversample: int):
    """Global splitters from an all-gathered per-shard sample."""
    n_local = local.shape[0]
    s = min(n_local, max(oversample, 1))
    stride = -(-n_local // s)  # ceil: sample must span the whole shard
    sample = jax.lax.stop_gradient(local[::stride])
    gathered = sample
    for ax in axis_names:
        gathered = jax.lax.all_gather(gathered, ax, tiled=True)
    gathered = jnp.sort(gathered)
    pos = (jnp.arange(1, num_shards) * gathered.shape[0]) // num_shards
    return gathered[pos]


def _paper_splitters(local: jax.Array, num_shards: int, axis_names):
    """§3.1 equal-width ranges from the *global* min/max (psum-free: pmax)."""
    lo, hi = jnp.min(local), jnp.max(local)
    for ax in axis_names:
        lo = jax.lax.pmin(lo, ax)
        hi = jax.lax.pmax(hi, ax)
    lo_f = lo.astype(jnp.float32)
    width = (hi.astype(jnp.float32) - lo_f) / num_shards
    width = jnp.where(width > 0, width, 1.0)
    edges = lo_f + width * jnp.arange(1, num_shards, dtype=jnp.float32)
    return edges.astype(local.dtype) if jnp.issubdtype(local.dtype, jnp.integer) else edges


def _bucket_exchange(local, splitters, num_shards, capacity, axis_name):
    """Scatter into per-destination rows and run one fused all_to_all."""
    ids = partition.splitter_bucket_ids(local, splitters)
    buckets, counts = partition.scatter_to_buckets(
        local, ids, num_shards, capacity, fill_value=_fill_value(local.dtype)
    )
    # (num_shards, capacity) — row d goes to device d.
    recv = jax.lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv_counts = jax.lax.all_to_all(
        counts, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    sent = jnp.sum(counts)  # elements actually shipped (≤ local n if overflow)
    return recv, recv_counts, sent


def _finalize(recv, recv_counts, local_sort):
    """Sort the received rows' concatenation; padded tail sorts to the end."""
    merged = local_sort(recv.ravel())
    return merged, jnp.sum(recv_counts)


def row_capacity(n: int, num_shards: int, capacity_factor: float) -> int:
    """Slots per (source, destination) row of the flat exchange of an
    ``n``-key array over ``num_shards`` shards: ``capacity_factor`` times a
    row's even share of its shard, rounded up to a multiple of 8."""
    capacity = int(capacity_factor * -(-(n // num_shards) // num_shards))
    return capacity + (-capacity) % 8


def dist_sort(
    x: jax.Array,
    *,
    mesh: Mesh,
    axis_names: Sequence[str] = ("data",),
    method: str = "sample",
    capacity_factor: float = 2.0,
    oversample: int = 64,
    local_sort=jnp.sort,
):
    """Globally sort ``x`` (sharded on its leading axis over ``axis_names``).

    Returns ``(values, counts)``: ``values`` is (devices * capacity,)
    globally sharded, each shard sorted and padded at its tail;
    ``counts`` is (devices,) the per-shard valid lengths.  Dropped-element
    detection: ``counts.sum() == x.size`` iff no capacity overflow.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    num_shards = 1
    for ax in axis_names:
        num_shards *= sizes[ax]
    n = x.shape[0]
    if n % num_shards:
        raise ValueError(f"n={n} not divisible by shard count {num_shards}")
    capacity = row_capacity(n, num_shards, capacity_factor)

    if method in ("sample", "paper", "valiant"):
        impl = functools.partial(
            _flat_impl,
            num_shards=num_shards,
            capacity=capacity,
            method=method,
            oversample=oversample,
            axis_names=tuple(axis_names),
            local_sort=local_sort,
        )
        spec = P(tuple(axis_names))
    elif method == "hier":
        if len(axis_names) < 2:
            raise ValueError("hier method needs (outer, inner) axes, e.g. ('pod','data')")
        impl = functools.partial(
            _hier_impl,
            axis_names=tuple(axis_names),
            sizes=tuple(sizes[a] for a in axis_names),
            capacity_factor=capacity_factor,
            oversample=oversample,
            local_sort=local_sort,
        )
        spec = P(tuple(axis_names))
    else:
        raise ValueError(f"unknown method {method!r}")

    fn = compat.shard_map(
        impl, mesh=mesh, in_specs=(spec,), out_specs=(spec, spec)
    )
    return fn(x)


def _flat_impl(local, *, num_shards, capacity, method, oversample, axis_names, local_sort):
    local = local.ravel()
    # Exchange runs over a single logical axis: if the shard spans several
    # mesh axes, they act as one flattened axis for all_to_all.
    ax = axis_names if len(axis_names) > 1 else axis_names[0]
    if method == "valiant":
        # hop 1: round-robin interleave — device d receives a stratified
        # 1/P sample from every source, destroying any value/order skew.
        n_local = local.shape[0]
        per = n_local // num_shards
        head = jax.lax.all_to_all(
            local[: per * num_shards].reshape(num_shards, per),
            ax, split_axis=0, concat_axis=0, tiled=True,
        ).ravel()
        # indivisible tail stays local (counted, never dropped)
        local = jnp.concatenate([head, local[per * num_shards :]])
    if method == "paper":
        splitters = _paper_splitters(local, num_shards, axis_names)
    else:
        splitters = _local_splitters(local, num_shards, axis_names, oversample)
    recv, recv_counts, _ = _bucket_exchange(local, splitters, num_shards, capacity, ax)
    merged, count = _finalize(recv, recv_counts, local_sort)
    return merged, count[None]


def _hier_impl(local, *, axis_names, sizes, capacity_factor, oversample, local_sort):
    """Two-level exchange: global splitters, but traffic crosses the slow
    (outer/pod) axis exactly once, then fans out on the fast inner axis.

    Stage 1 (optical, once): bucket by destination *pod* and all_to_all over
    the pod axis.  Stage 2 (electrical): bucket by destination device within
    the pod and all_to_all over the inner axis.  Equivalent result to the
    flat exchange; traffic on the slow tier is minimal and contiguous.
    """
    outer_ax, inner_ax = axis_names[0], axis_names[1:]
    outer_n = sizes[0]
    inner_n = 1
    for s in sizes[1:]:
        inner_n *= s
    num_shards = outer_n * inner_n
    local = local.ravel()
    n_local = local.shape[0]

    splitters = _local_splitters(local, num_shards, axis_names, oversample)
    # ---- stage 1: route to the destination pod (outer axis), one crossing.
    pod_splitters = splitters[inner_n - 1 :: inner_n]  # every inner_n-th → pod edges
    cap1 = int(capacity_factor * -(-n_local // outer_n))
    cap1 += (-cap1) % 8
    recv1, cnt1, _ = _bucket_exchange(local, pod_splitters, outer_n, cap1, outer_ax)
    # Compact: received rows concatenated; invalid slots are fill (sort last).
    stage1 = recv1.ravel()
    valid1 = jnp.sum(cnt1)

    # ---- stage 2: inside the pod, route to the destination device.
    my_pod = jax.lax.axis_index(outer_ax)
    inner_splitters = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([splitters, splitters[-1:]]), my_pod * inner_n, inner_n
    )[: inner_n - 1]
    cap2 = int(capacity_factor * -(-stage1.shape[0] // inner_n))
    cap2 += (-cap2) % 8
    inner = inner_ax if len(inner_ax) > 1 else inner_ax[0]
    ids = partition.splitter_bucket_ids(stage1, inner_splitters)
    # Fill slots from stage 1 carry the dtype max; they bucket to the last
    # device — mask them to an overflow row instead so counts stay exact.
    pos = jnp.arange(stage1.shape[0])
    is_valid = pos < 0  # placeholder; recompute validity via counts layout
    # stage1 layout: outer_n rows of cap1; row r has cnt1[r] valid entries.
    row, col = jnp.divmod(pos, cap1)
    is_valid = col < cnt1[row]
    ids = jnp.where(is_valid, ids, inner_n)  # inner_n = drop row
    buckets, counts = partition.scatter_to_buckets(
        jnp.where(is_valid, stage1, _fill_value(stage1.dtype)),
        ids,
        inner_n + 1,
        cap2,
        fill_value=_fill_value(stage1.dtype),
    )
    buckets, counts = buckets[:inner_n], counts[:inner_n]
    recv2 = jax.lax.all_to_all(buckets, inner, split_axis=0, concat_axis=0, tiled=True)
    cnt2 = jax.lax.all_to_all(counts, inner, split_axis=0, concat_axis=0, tiled=True)
    merged, count = _finalize(recv2, cnt2, local_sort)
    del valid1
    return merged, count[None]


def host_check_globally_sorted(values, counts) -> bool:
    """Host-side validation of the output contract."""
    import numpy as np

    values = np.asarray(values)
    counts = np.asarray(counts).ravel()
    shards = np.split(values, counts.size)
    prev_max = None
    for shard, c in zip(shards, counts):
        valid = np.sort(shard)[: int(c)]  # shard is sorted with fill at tail
        if not np.all(valid[:-1] <= valid[1:]):
            return False
        if prev_max is not None and valid.size and prev_max > valid[0]:
            return False
        if valid.size:
            prev_max = valid[-1]
    return True
