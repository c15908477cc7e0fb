"""Mixture-of-Experts with sort-based dispatch — the paper's technique
as a first-class framework feature.

Expert routing *is* the Array Division Procedure (§3.1) with
``SubDivider = 1``: each (token, expert-choice) assignment is an element
whose "value" is its expert id; bucketing assignments by expert id and
laying each bucket out contiguously is exactly the paper's value-range
partition, and the merge-free gather property becomes the contiguous
(expert, capacity) buffer the grouped FFN matmul wants.

``dispatch='sorted'`` uses ``repro.core.partition`` bucket counts/ranks
(the one-hot histogram + exclusive cumsum) to compute, for every
assignment, its slot in the (E, C, d) dispatch buffer — histogram + stable
rank, no data-dependent control flow.  ``dispatch='argsort'`` computes the
same ranks from ONE stable argsort of the expert ids (position minus
group start) — the ``SortEngine.sort_pairs`` permutation-gather
formulation in-graph, O(A log A) instead of the one-hot O(A·E), with
bit-identical outputs (DESIGN.md §12; the before/after lives in
``benchmarks/bench_workloads.py``).  ``dispatch='dense'`` is the one-hot
einsum baseline (tiny shapes / numerics oracle).

Sharding: expert-parallel (experts → tensor axis) when ``E % tp == 0``,
else tensor-parallel on d_ff.  On the multi-pod mesh the (E,C,d) buffer's
token dim additionally shards over the batch axes, giving the hierarchical
"cross the pod axis once" exchange when XLA partitions the gather/scatter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import partition as core_partition
from repro.models.common import AxisRules, dense_init, shard, split_keys


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    keys = split_keys(key, 7)
    p = {
        "router": dense_init(keys[0], (d, m.num_experts), 0, cfg.param_dtype),
        "wi": dense_init(keys[1], (m.num_experts, d, m.expert_d_ff), 1, cfg.param_dtype),
        "wg": dense_init(keys[2], (m.num_experts, d, m.expert_d_ff), 1, cfg.param_dtype),
        "wo": dense_init(keys[3], (m.num_experts, m.expert_d_ff, d), 1, cfg.param_dtype),
    }
    if m.num_shared_experts:
        ff = m.shared_d_ff * m.num_shared_experts
        p["shared_wi"] = dense_init(keys[4], (d, ff), 0, cfg.param_dtype)
        p["shared_wg"] = dense_init(keys[5], (d, ff), 0, cfg.param_dtype)
        p["shared_wo"] = dense_init(keys[6], (ff, d), 0, cfg.param_dtype)
    return p


def moe_specs(cfg, tp_size: int) -> dict:
    m = cfg.moe
    ep = m.num_experts % max(tp_size, 1) == 0 and tp_size > 1
    if ep:
        e_wi = P("tensor", "fsdp", None)
        e_wo = P("tensor", None, "fsdp")
    else:
        e_wi = P(None, "fsdp", "tensor")
        e_wo = P(None, "tensor", "fsdp")
    s = {"router": P("fsdp", None), "wi": e_wi, "wg": e_wi, "wo": e_wo}
    if m.num_shared_experts:
        s["shared_wi"] = P("fsdp", "tensor")
        s["shared_wg"] = P("fsdp", "tensor")
        s["shared_wo"] = P("tensor", "fsdp")
    return s


def _router(p, x, cfg):
    """Top-k routing: probs, expert ids, aux load-balance loss."""
    m = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cfg.dtype)).astype(
        jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.num_experts_per_tok)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    # Switch-style aux loss: E · Σ_e f_e · P_e
    token_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32), axis=2),
        axis=(0, 1),
    ) / m.num_experts_per_tok
    prob_frac = jnp.mean(probs, axis=(0, 1))
    aux = m.num_experts * jnp.sum(token_frac * prob_frac) * m.router_aux_loss
    return top_p, top_e, aux


def _expert_ffn(p, xs, cfg):
    """Grouped FFN over the (E, C, d) dispatch buffer."""
    dt = cfg.dtype
    h = jnp.einsum("ecd,edf->ecf", xs, p["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xs, p["wg"].astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"].astype(dt))


def _moe_shard_map(p, x, cfg, rules, top_p, top_e):
    """shard_map dispatch (§Perf lever, dispatch='shard_map').

    The pjit scatter/gather dispatch replicates the (E,C,d) buffer and
    all-reduces it (SPMD scatter with data-dependent indices can't be
    partitioned).  Here tokens NEVER leave their device: each TP rank
    holds a d_ff-slice of every expert, builds its bucket buffer from
    LOCAL tokens only (the Array Division Procedure runs per shard),
    computes partial expert outputs, combines locally, and one psum over
    the TP axis finishes the job.  Inter-pod traffic: ZERO (tokens stay
    pod-local) — the paper's "cross the optical tier once" ideal, beaten:
    the optical tier isn't crossed at all.
    """
    m = cfg.moe
    mesh = compat.get_ambient_mesh()
    if mesh is None or not mesh.shape or rules.tensor not in mesh.shape:
        # no mesh context (CPU tests): same math, local
        return None
    B, S, d = x.shape
    k = m.num_experts_per_tok
    batch_axes = rules.batch or ()
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    if B % max(bsz, 1):
        return None
    T_loc = (B // max(bsz, 1)) * S
    cap = int(-(-T_loc * k * m.capacity_factor // m.num_experts))
    cap += (-cap) % 8
    tensor_ax = rules.tensor

    def local(x_loc, tp_loc, te_loc, wi, wg, wo):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        flat_e = te_loc.reshape(T * k)
        flat_w = tp_loc.reshape(T * k).astype(jnp.float32)
        tok_idx = jnp.repeat(jnp.arange(T), k)
        ranks = core_partition.bucket_ranks(flat_e, m.num_experts)
        keep = ranks < cap
        slot = jnp.where(keep, flat_e * cap + ranks, m.num_experts * cap)
        xt = x_loc.reshape(T, d)
        buf = jnp.zeros((m.num_experts * cap + 1, d), cfg.dtype)
        buf = buf.at[slot].set(xt[tok_idx])[:-1].reshape(m.num_experts, cap, d)
        dt = cfg.dtype
        h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(dt))
        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        part = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo.astype(dt))
        part = part.reshape(m.num_experts * cap, d)
        contrib = jnp.concatenate([part, jnp.zeros((1, d), part.dtype)])[
            jnp.where(keep, slot, m.num_experts * cap)
        ]
        y = jnp.zeros((T, d), jnp.float32)
        y = y.at[tok_idx].add(contrib.astype(jnp.float32) * flat_w[:, None])
        # d_ff is sliced over the TP axis → partial sums; one psum finishes
        y = jax.lax.psum(y, tensor_ax)
        return y.reshape(Bl, Sl, d).astype(cfg.dtype)

    from jax.sharding import PartitionSpec as PS

    bspec = PS(batch_axes or None, None, None)
    out = compat.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            bspec,
            bspec,
            bspec,
            PS(None, None, tensor_ax),
            PS(None, None, tensor_ax),
            PS(None, tensor_ax, None),
        ),
        out_specs=bspec,
    )(x, top_p, top_e, p["wi"], p["wg"], p["wo"])
    return out


def apply_moe(p, x, cfg, rules: AxisRules):
    """Returns (y, aux_loss).  x: (B, S, d)."""
    m = cfg.moe
    B, S, d = x.shape
    top_p, top_e, aux = _router(p, x, cfg)

    if m.dispatch == "dense":
        # oracle path: every expert runs on every token
        one_hot = jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32)
        gates = jnp.sum(one_hot * top_p[..., None], axis=2)  # (B,S,E)
        h = jnp.einsum("bsd,edf->bsef", x, p["wi"].astype(cfg.dtype))
        g = jnp.einsum("bsd,edf->bsef", x, p["wg"].astype(cfg.dtype))
        y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * h, p["wo"].astype(cfg.dtype))
        y = jnp.einsum("bsed,bse->bsd", y.astype(jnp.float32), gates).astype(cfg.dtype)
    elif m.dispatch == "shard_map":
        y = _moe_shard_map(p, x, cfg, rules, top_p, top_e)
        if y is None:  # no mesh (CPU tests) → same math via the pjit path
            cfg2 = cfg.replace(moe=cfg.moe.__class__(
                **{**cfg.moe.__dict__, "dispatch": "sorted"}))
            return apply_moe(p, x, cfg2, rules)  # incl. shared experts
    elif m.dispatch in ("sorted", "argsort"):
        T = B * S
        k = m.num_experts_per_tok
        A = T * k  # total assignments
        cap = int(-(-A * m.capacity_factor // m.num_experts))
        cap += (-cap) % 8
        flat_e = top_e.reshape(A)  # assignment → expert id ("value" to bucket)
        flat_w = top_p.reshape(A).astype(jnp.float32)
        tok_idx = jnp.repeat(jnp.arange(T), k)
        counts = core_partition.bucket_counts(flat_e, m.num_experts)
        if m.dispatch == "argsort":
            # --- sort_pairs formulation: ONE stable argsort groups the
            # assignments by expert, and each rank is its position minus
            # its expert's group start — O(A log A) against 'sorted''s
            # O(A·E) one-hot rank matrix, the in-graph twin of
            # ``SortEngine.sort_pairs``' permutation gather (DESIGN.md
            # §12).  jnp.argsort is stable, so ranks keep order-of-
            # appearance and the outputs are bit-identical to 'sorted'.
            order = jnp.argsort(flat_e)
            starts = jnp.cumsum(counts) - counts
            ranks_sorted = (
                jnp.arange(A, dtype=jnp.int32) - starts[flat_e[order]]
            )
            ranks = jnp.zeros(A, jnp.int32).at[order].set(ranks_sorted)
        else:
            # --- Array Division: histogram + stable rank per bucket -----
            ranks = core_partition.bucket_ranks(flat_e, m.num_experts)
        keep = ranks < cap
        slot = jnp.where(keep, flat_e * cap + ranks, m.num_experts * cap)
        # dispatch buffer (E*C, d): gather token vectors into bucket order
        xt = x.reshape(T, d)
        buf = jnp.zeros((m.num_experts * cap + 1, d), cfg.dtype)
        buf = buf.at[slot].set(xt[tok_idx])[:-1]
        buf = buf.reshape(m.num_experts, cap, d)
        if m.dispatch_sharded:
            e_ax = "tensor" if m.expert_parallel else None
            buf = shard(buf, rules, e_ax, "batch", None)
            ye = _expert_ffn(p, buf, cfg)
            ye = shard(ye, rules, e_ax, "batch", None).reshape(
                m.num_experts * cap, d
            )
        else:
            buf = shard(buf, rules, "tensor", None, None)
            ye = _expert_ffn(p, buf, cfg).reshape(m.num_experts * cap, d)
        # combine: weighted scatter-add back to tokens
        contrib = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)])[
            jnp.where(keep, slot, m.num_experts * cap)
        ]
        y = jnp.zeros((T, d), jnp.float32)
        y = y.at[tok_idx].add(contrib.astype(jnp.float32) * flat_w[:, None])
        y = y.reshape(B, S, d).astype(cfg.dtype)
        del counts
    else:
        raise ValueError(f"unknown dispatch {m.dispatch!r}")

    if m.num_shared_experts:
        dt = cfg.dtype
        h = jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(dt))
        g = jnp.einsum("bsd,df->bsf", x, p["shared_wg"].astype(dt))
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["shared_wo"].astype(dt))
    y = shard(y, rules, "batch", "seq", None)
    return y, aux
