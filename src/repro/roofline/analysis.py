"""Three-term roofline from a compiled dry-run artifact.

    compute    = HLO_FLOPs(per device)        / peak_FLOP/s
    memory     = HLO_bytes(per device)        / HBM_bw
    collective = collective_bytes(per device) / link_bw   (per link class)

``cost_analysis()`` on a partitioned computation reports **per-device**
flops/bytes (verified against a hand-checked einsum).  Collective traffic
is not in cost_analysis — we parse the post-SPMD optimized HLO
(``compiled.as_text()``): every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute op's *output shape* bytes are accumulated,
split by whether its replica group spans the pod axis (inter-pod = slow
"optical" tier) or stays inside a pod (ICI).

Inter-pod detection: with mesh (pod=2, data=16, model=16) laid out
major-to-minor, two device ids in the same group that differ by ≥ 256 can
only be in different pods.
"""

from __future__ import annotations

import re

from repro.roofline.hw import HW, V5E

COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(?:\(([^)]*)\)|([a-z0-9\[\],{}<>= ]+?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]")
GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")
PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*)\}")

DTYPE_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _line_bytes(line: str) -> int:
    """Bytes of every tensor in the op's output shape(s)."""
    # only look at the segment before the operand list's '(' to avoid
    # counting operand shapes; the '=' left side has the output shape(s).
    head = line.split("(", 1)[0]
    total = 0
    for dt, dims in SHAPE_RE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _crosses_pod(line: str, pod_block: int) -> bool:
    """Does this collective's group span device-id blocks of `pod_block`?"""
    m = GROUPS_RE.search(line)
    if m:
        n_groups, g_size = int(m.group(1)), int(m.group(2))
        # iota groups: consecutive-ids <=[perm] — group spans pods iff its
        # id-range covers more than one pod block under the transpose.  A
        # conservative exact check: reconstruct the first group.
        dims = [int(x) for x in m.group(3).split(",")]
        total = 1
        for d in dims:
            total *= d
        if g_size > 1:
            # devices in one group under iota layout differ by strides of
            # the minor axes; group crosses pods iff g_size * stride
            # reaches beyond a pod block.  Parse transpose if present.
            tmatch = re.search(r"T\(([\d,]+)\)", line)
            import numpy as np

            ids = np.arange(total)
            if tmatch:
                perm = [int(x) for x in tmatch.group(1).split(",")]
                ids = ids.reshape(dims).transpose(perm).reshape(-1)
            groups = ids.reshape(n_groups, g_size)
            return bool((groups // pod_block != groups[:, :1] // pod_block).any())
        return False
    m = GROUPS_LIST_RE.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in re.findall(r"\d+", grp)]
            if ids and (max(ids) // pod_block) != (min(ids) // pod_block):
                return True
        return False
    m = PAIRS_RE.search(line)
    if m:
        for pair in re.findall(r"\{(\d+),(\d+)\}", "{" + m.group(1) + "}"):
            a, b = int(pair[0]), int(pair[1])
            if a // pod_block != b // pod_block:
                return True
    return False


def _head_shapes(line: str):
    head = line.split("(", 1)[0]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in SHAPE_RE.findall(head)]


def collective_bytes(
    hlo_text: str,
    *,
    num_devices: int,
    pod_block: int | None = None,
    halve_param_shapes: "set[tuple[int, ...]] | None" = None,
):
    """Sum collective op bytes from post-SPMD HLO.

    Returns dict with total/intra/inter bytes (PER DEVICE — HLO shapes in
    SPMD are already the per-device shard shapes) and per-op-kind totals.

    ``halve_param_shapes``: CPU-backend correction.  The CPU XLA backend
    upcasts bf16 dots to f32 and hoists the convert BEFORE weight
    all-gathers / gradient all-reduces, so with bf16 params the HLO still
    shows f32 weight collectives (2× the TPU bytes).  When the caller
    intends bf16 params, pass the set of (full and transposed) parameter
    shapes; f32 collectives whose tensor shape matches are counted at
    half width.  Applied mechanically and identically across baseline and
    optimized variants — deltas remain meaningful.
    """
    out = {"total": 0, "intra_pod": 0, "inter_pod": 0, "by_kind": {}, "count": 0,
           "halved": 0}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.match(line)
        if not m:
            continue
        kind = m.group(3)
        b = _line_bytes(line)
        if halve_param_shapes:
            for dt, shp in _head_shapes(line):
                if dt == "f32" and shp in halve_param_shapes:
                    cut = (np_prod(shp) * 4) // 2
                    b -= cut
                    out["halved"] += cut
        out["total"] += b
        out["count"] += 1
        out["by_kind"][kind] = out["by_kind"].get(kind, 0) + b
        if pod_block and _crosses_pod(line, pod_block):
            out["inter_pod"] += b
        else:
            out["intra_pod"] += b
    return out


def np_prod(shp):
    n = 1
    for d in shp:
        n *= d
    return n


def param_shape_set(params_shape_tree) -> set:
    """Full + transposed 2-D(+) parameter shapes for the CPU-upcast fix."""
    import jax

    out = set()
    for leaf in jax.tree.leaves(params_shape_tree):
        shp = tuple(int(x) for x in leaf.shape)
        if len(shp) >= 2:
            out.add(shp)
            out.add(tuple(reversed(shp)))
            # layer-stacked variants appear unstacked in unrolled HLO
            if len(shp) >= 3:
                out.add(shp[1:])
                out.add(tuple(reversed(shp[1:])))
    return out


def bound_time_s(
    *,
    flops: float = 0.0,
    bytes_moved: float = 0.0,
    intra_pod_bytes: float = 0.0,
    inter_pod_bytes: float = 0.0,
    hw: HW = V5E,
) -> float:
    """Roofline lower bound on wall time for an abstract workload.

    The same three-term max as :func:`roofline_from_compiled`, but over
    caller-supplied workload numbers instead of a compiled artifact — the
    shared arithmetic behind the perf subsystem's machine normalization
    (``repro.perf.normalize``, DESIGN.md §9).
    """
    t_compute = flops / hw.peak_bf16_flops if flops else 0.0
    t_memory = bytes_moved / hw.hbm_bw if bytes_moved else 0.0
    t_coll = 0.0
    if intra_pod_bytes:
        t_coll += intra_pod_bytes / hw.ici_bw
    if inter_pod_bytes:
        t_coll += inter_pod_bytes / hw.inter_pod_bw
    return max(t_compute, t_memory, t_coll)


def roofline_from_compiled(
    compiled,
    *,
    num_devices: int,
    pod_block: int | None = None,
    hw: HW = V5E,
    model_flops: float | None = None,
) -> dict:
    """The §Roofline record for one (arch × shape × mesh) cell."""
    ca = compiled.cost_analysis()
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, num_devices=num_devices, pod_block=pod_block)
    t_compute = flops_dev / hw.peak_bf16_flops
    t_memory = bytes_dev / hw.hbm_bw
    t_coll = coll["intra_pod"] / hw.ici_bw + coll["inter_pod"] / hw.inter_pod_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mem = compiled.memory_analysis()
    rec = {
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "bound_time_s": max(terms.values()),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
    }
    if model_flops is not None:
        total_hlo_flops = flops_dev * num_devices
        rec["model_flops"] = model_flops
        rec["useful_flops_ratio"] = model_flops / total_hlo_flops if total_hlo_flops else 0.0
        rec["mfu_bound"] = (
            (model_flops / num_devices / hw.peak_bf16_flops) / rec["bound_time_s"]
            if rec["bound_time_s"] > 0
            else 0.0
        )
    return rec


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D train; 2·N·D_active per generated token batch for
    decode; 2·N·D for prefill.  MoE uses active params."""
    n = cfg.param_count()
    if cfg.is_moe:
        m = cfg.moe
        total_e = 3 * cfg.d_model * m.expert_d_ff * m.num_experts
        active_e = 3 * cfg.d_model * m.expert_d_ff * m.num_experts_per_tok
        n = n - cfg.num_layers * (total_e - active_e)
    d_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * d_tokens
