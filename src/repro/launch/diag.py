"""Diagnostic: compile a small unrolled variant of a cell and print the
largest collective ops and buffer-traffic sources from the optimized HLO.

    PYTHONPATH=src python -m repro.launch.diag --arch X --shape Y [--levers ...]
"""
import os  # noqa: E402

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import re  # noqa: E402

import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.base import SHAPES, RunConfig  # noqa: E402
from repro.launch.dryrun import GRAD_ACCUM, LEVERS, _scaled_cfg, build_lowered  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import sharding as SH  # noqa: E402
from repro.roofline.analysis import COLLECTIVE_RE, SHAPE_RE, DTYPE_BYTES, _line_bytes  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--levers", default="")
    ap.add_argument("--layers", type=int, default=0, help="0 → one period")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch)
    run_kw = {}
    for lv in [x for x in args.levers.split(",") if x]:
        cfg, run_kw = LEVERS[lv](cfg, run_kw)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    from repro.launch.dryrun import _layer_period

    L1 = args.layers or _layer_period(cfg)
    cfg1 = _scaled_cfg(cfg, L1, scan=False)
    if shape.kind == "train":
        sizes = SH.mesh_axis_sizes(mesh)
        bs = int(np.prod([sizes.get(a, 1) for a in ("pod", "data")]))
        a_eff = max(1, min(GRAD_ACCUM.get(args.arch, 1), shape.global_batch // bs))
        shape = dataclasses.replace(shape, global_batch=shape.global_batch // a_eff)
    run = RunConfig(model=cfg1, shape=shape, grad_accum=1, **run_kw)
    lowered, _ = build_lowered(cfg1, shape, mesh, run)
    compiled = lowered.compile()
    txt = compiled.as_text()

    # ---- largest collectives
    colls = []
    for line in txt.splitlines():
        m = COLLECTIVE_RE.match(line)
        if m:
            colls.append((_line_bytes(line), m.group(3), line.strip()[:240]))
    colls.sort(reverse=True)
    print(f"=== top collectives ({L1} layers, A=1) — per-device output bytes")
    for b, kind, line in colls[: args.top]:
        print(f"{b/1e6:10.1f} MB  {kind:18s} {line[:170]}")
    total = sum(b for b, _, _ in colls)
    by_kind = collections.Counter()
    for b, kind, _ in colls:
        by_kind[kind] += b
    print(f"total collective: {total/1e9:.2f} GB   by kind:",
          {k: f"{v/1e9:.2f}GB" for k, v in by_kind.items()})

    # ---- largest single ops by output bytes (traffic proxy)
    ops = []
    for line in txt.splitlines():
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*", s)
        if not m or "fusion" in s[:60] or "parameter(" in s:
            continue
        b = _line_bytes(s)
        if b > 0:
            opname = s.split("=", 1)[1].strip().split("(")[0].split(" ")[-1]
            ops.append((b, opname, s[:170]))
    ops.sort(reverse=True)
    print(f"\n=== top non-fusion ops by output bytes")
    seen = collections.Counter()
    shown = 0
    for b, op, line in ops:
        if seen[op] >= 3:
            continue
        seen[op] += 1
        print(f"{b/1e6:10.1f} MB  {line[:170]}")
        shown += 1
        if shown >= args.top:
            break

    ca = compiled.cost_analysis()
    print(f"\nflops={ca.get('flops',0):.3e}  bytes={ca.get('bytes accessed',0):.3e}")
    mem = compiled.memory_analysis()
    print(f"temp={mem.temp_size_in_bytes/1e9:.2f}GB arg={mem.argument_size_in_bytes/1e9:.2f}GB")


if __name__ == "__main__":
    main()
