"""shard_map-level collective tricks for the slow (inter-pod) tier.

The paper's core scheduling insight — do the heavy lifting on the cheap
electrical links, cross the optical tier once — maps to these two
primitives:

* ``hierarchical_psum``: reduce-scatter inside the pod (fast axis), ONE
  all-reduce across pods on the 1/|pod-axis|-sized shard, all-gather
  inside the pod.  Inter-pod bytes drop from full-tensor to
  full-tensor / intra_pod_size.
* ``int8_psum``: QSGD-style quantise → integer psum → dequantise, for
  gradient reductions where 4× fewer bytes beat the quantisation noise
  (pair with error feedback from repro.optim.compression).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def int8_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """Quantised psum (inside shard_map).  int32 accumulation, f32 scale."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
    # every participant must use the SAME scale → max-reduce the scales
    scale = jax.lax.pmax(scale, axis_name)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int32)
    summed = jax.lax.psum(q, axis_name)
    return (summed.astype(jnp.float32) * scale).astype(x.dtype)


def hierarchical_psum(x: jax.Array, *, fast_axis: str, slow_axis: str) -> jax.Array:
    """psum over (fast × slow) with minimal slow-axis traffic.

    reduce_scatter(fast) → psum(slow) on the shard → all_gather(fast).
    Equivalent to ``psum(x, (fast, slow))`` but the slow tier carries
    1/|fast| of the bytes — the paper's optical-tier economy.
    """
    n_fast = jax.lax.axis_size(fast_axis)
    lead = x.shape[0]
    if lead % n_fast:
        # fall back for indivisible leading dims
        return jax.lax.psum(x, (fast_axis, slow_axis))
    shard = jax.lax.psum_scatter(x, fast_axis, scatter_dimension=0, tiled=True)
    shard = jax.lax.psum(shard, slow_axis)
    return jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)
