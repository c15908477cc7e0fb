"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production mesh, prove it fits, extract calibrated roofline terms.

MUST be the very first two lines — jax locks the device count on first
init, and only this entrypoint may see 512 devices:
"""
import os  # noqa: E402

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


from repro.configs import registry  # noqa: E402
from repro.configs.base import SHAPES, ModelConfig, RunConfig, ShapeConfig  # noqa: E402
from repro.launch import sharding as SH  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline.analysis import (  # noqa: E402
    collective_bytes,
    model_flops_for,
    roofline_from_compiled,
)
from repro.roofline.hw import V5E  # noqa: E402

# Microbatches per train step, sized so per-device activation memory
# (layers × tokens/dev × d_model × 2B under per-layer remat) stays well
# inside the 16 GB v5e HBM.  Effective value is min(this, B/batch_shards).
GRAD_ACCUM = {
    "whisper-tiny": 1,
    "mixtral-8x22b": 16,
    "deepseek-v2-lite-16b": 4,
    "minitron-4b": 8,
    "qwen1.5-32b": 16,
    "qwen1.5-110b": 16,
    "gemma3-4b": 8,
    "mamba2-370m": 2,
    "qwen2-vl-7b": 8,
    "zamba2-2.7b": 8,
}

# =============================================================== lowering
def _layer_period(cfg: ModelConfig) -> int:
    if cfg.is_hybrid:
        return cfg.hybrid_period
    if cfg.window_pattern:
        return len(cfg.window_pattern)
    return 1


def _scaled_cfg(cfg: ModelConfig, n_layers: int, scan: bool) -> ModelConfig:
    kw = {"num_layers": n_layers, "scan_layers": scan}
    if cfg.family == "encdec":
        kw["encoder_layers"] = max(
            1, cfg.encoder_layers * n_layers // max(cfg.num_layers, 1)
        )
    return cfg.replace(**kw)


def build_lowered(cfg, shape, mesh, run, *, cache_len=None):
    """Lower one computation (train/prefill/decode) on `mesh`.  Returns
    (lowered, rules)."""
    rules = SH.rules_for(cfg, shape, mesh)
    model_api = registry.get_model_api(cfg)
    in_specs = registry.input_specs(cfg, shape)
    bspecs = SH.sanitize_specs(SH.batch_specs(cfg, shape, rules), in_specs, mesh)
    tp = SH.mesh_axis_sizes(mesh).get("model", 1)
    key = jax.random.PRNGKey(0)
    pspecs_l = model_api.param_specs(cfg, rules, tp)
    params_shape = jax.eval_shape(lambda: model_api.init(key, cfg))
    pspecs = SH.sanitize_specs(pspecs_l, params_shape, mesh)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.train.train_step import init_train_state, make_train_step

            state_shape = jax.eval_shape(
                lambda: init_train_state(key, cfg, run, model_api)
            )
            opt_specs = {"m": pspecs, "v": pspecs, "count": P()}
            if run.master_weights:
                opt_specs["master"] = pspecs
            sspecs = {"params": pspecs, "opt": opt_specs, "step": P()}
            if run.grad_compression == "int8":
                sspecs["error_fb"] = pspecs
            gspecs = pspecs if getattr(run, "_grad_specs_flag", False) else None
            step = make_train_step(cfg, run, model_api, rules, grad_specs=gspecs)
            jitted = jax.jit(
                step,
                in_shardings=(SH.named(sspecs, mesh), SH.named(bspecs, mesh)),
                out_shardings=(SH.named(sspecs, mesh), None),
                donate_argnums=(0,),
            )
            return jitted.lower(state_shape, in_specs), rules
        cache_len = cache_len or shape.seq_len + 16
        cache_shape = jax.eval_shape(
            lambda: model_api.init_cache(cfg, shape.global_batch, cache_len)
        )
        cspecs = SH.sanitize_specs(
            SH.cache_specs(cfg, rules, cache_shape), cache_shape, mesh
        )
        if shape.kind == "prefill":
            fn = lambda p, b, c: model_api.prefill(p, b, cfg, rules, c)
            jitted = jax.jit(
                fn,
                in_shardings=(
                    SH.named(pspecs, mesh),
                    SH.named(bspecs, mesh),
                    SH.named(cspecs, mesh),
                ),
                out_shardings=(None, SH.named(cspecs, mesh)),
                donate_argnums=(2,),
            )
            return jitted.lower(params_shape, in_specs, cache_shape), rules
        fn = lambda p, t, c, pos: model_api.decode_step(p, t, cfg, rules, c, pos)
        jitted = jax.jit(
            fn,
            in_shardings=(
                SH.named(pspecs, mesh),
                SH.named(bspecs["tokens"], mesh),
                SH.named(cspecs, mesh),
                None,
            ),
            out_shardings=(None, SH.named(cspecs, mesh)),
            donate_argnums=(2,),
        )
        return (
            jitted.lower(
                params_shape, in_specs["tokens"], cache_shape,
                jax.ShapeDtypeStruct((), jnp.int32),
            ),
            rules,
        )


# ============================================================ calibration
def _measure(cfg, shape, mesh, run, *, pod_block):
    """Compile a (small) variant and pull raw per-device cost numbers.

    CPU-upcast fix: when params are intended bf16 (master_weights), f32
    weight-shaped collectives are counted at half width — see
    roofline.analysis.collective_bytes."""
    lowered, _ = build_lowered(cfg, shape, mesh, run)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    halve = None
    if run.master_weights:
        from repro.roofline.analysis import param_shape_set

        api = registry.get_model_api(cfg)
        halve = param_shape_set(
            jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
        )
    coll = collective_bytes(
        compiled.as_text(), num_devices=mesh.devices.size, pod_block=pod_block,
        halve_param_shapes=halve,
    )
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_intra": float(coll["intra_pod"]),
        "coll_inter": float(coll["inter_pod"]),
    }


def _combine(base, per_layer, n_extra, mult=1.0):
    return {
        k: max(0.0, mult * (base[k] + n_extra * per_layer[k])) for k in base
    }


def calibrated_costs(arch, cfg, shape, mesh, *, a_eff, pod_block, run_kw=None):
    """True per-step per-device costs via small UNROLLED lowers.

    XLA cost_analysis counts a while-loop body once, so the full-config
    numbers undercount by the layer count (and microbatch count).  We
    compile L1- and L2-layer unrolled variants (and an A=2 unrolled
    microbatch variant for train) and reconstruct:

        per_layer = (X(L2) − X(L1)) / (L2 − L1)
        train:  per_step = 2·X(L1,A1) − X(L1,A2);  per_mb = X(L1,A2) − X(L1,A1)
                total = per_step + A·(per_mb + (L−L1)·per_layer)
        serve:  total = X(L1) + (L − L1)·per_layer
    """
    period = _layer_period(cfg)
    L1, L2 = period, 2 * period
    # fractional period units so non-multiple depths (gemma3: 34 = 5×6+4)
    # extrapolate exactly by layer count
    extra_units = (cfg.num_layers - L1) / period
    c1 = _scaled_cfg(cfg, L1, scan=False)
    c2 = _scaled_cfg(cfg, L2, scan=False)
    if shape.kind == "train":
        mb = shape.global_batch // a_eff
        sh1 = dataclasses.replace(shape, global_batch=mb)
        sh2 = dataclasses.replace(shape, global_batch=2 * mb)
        run_kw = dict(run_kw or {})
        gflag = run_kw.pop("_grad_specs", False)
        run1 = RunConfig(model=c1, shape=sh1, grad_accum=1, **run_kw)
        runA = RunConfig(model=c1, shape=sh2, grad_accum=2, grad_accum_unroll=True,
                         **run_kw)
        for r_ in (run1, runA):
            object.__setattr__(r_, "_grad_specs_flag", gflag)
        x1 = _measure(c1, sh1, mesh, run1, pod_block=pod_block)
        run2 = RunConfig(model=c2, shape=sh1, grad_accum=1, **run_kw)
        object.__setattr__(run2, "_grad_specs_flag", gflag)
        x2 = _measure(c2, sh1, mesh, run2, pod_block=pod_block)
        xa = _measure(c1, sh2, mesh, runA, pod_block=pod_block)
        per_layer = {k: (x2[k] - x1[k]) / (L2 - L1) * period for k in x1}
        per_step = {k: max(0.0, 2 * x1[k] - xa[k]) for k in x1}
        per_mb = {k: max(0.0, xa[k] - x1[k]) for k in x1}
        total = {
            k: per_step[k]
            + a_eff * (per_mb[k] + extra_units * per_layer[k])
            for k in x1
        }
        return total, {"L1": L1, "L2": L2, "a_eff": a_eff, "x1": x1, "x2": x2, "xa": xa}
    run_kw = dict(run_kw or {})
    run_kw.pop("_grad_specs", None)
    run1 = RunConfig(model=c1, shape=shape, **run_kw)
    x1 = _measure(c1, shape, mesh, run1, pod_block=pod_block)
    x2 = _measure(c2, shape, mesh, RunConfig(model=c2, shape=shape, **run_kw),
                  pod_block=pod_block)
    per_layer = {k: (x2[k] - x1[k]) / (L2 - L1) * period for k in x1}
    total = _combine(x1, per_layer, extra_units)
    return total, {"L1": L1, "L2": L2, "x1": x1, "x2": x2}


# ================================================================= orchestration
def _lv_moefix(cfg, run_kw):
    return cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch_sharded=True)), run_kw


def _lv_moesm(cfg, run_kw):
    return cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch="shard_map")), run_kw


LEVERS = {
    # §Perf levers: name → fn(cfg, run_kw) -> (cfg', run_kw')
    "bf16mm": lambda c, r: (c.replace(attn_matmul_bf16=True), r),
    "inscan": lambda c, r: (c.replace(prefill_inscan_cache=True), r),
    "master": lambda c, r: (c, {**r, "master_weights": True}),
    "chunk4k": lambda c, r: (c.replace(attn_chunk=4096), r),
    "moefix": _lv_moefix,
    "moesm": _lv_moesm,
    "wincache": lambda c, r: (c.replace(decode_window_cache=True), r),
    "gradrs": lambda c, r: (c, {**r, "_grad_specs": True}),
    "accum8": lambda c, r: (c, {**r, "_grad_accum": 8}),
    # revert production defaults to the paper-faithful baseline
    "paperbase": lambda c, r: (
        c.replace(
            decode_window_cache=False,
            moe=dataclasses.replace(c.moe, dispatch="sorted", dispatch_sharded=False)
            if c.moe.num_experts else c.moe,
        ),
        r,
    ),
}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, calibrate=True,
               levers: tuple = ()):
    cfg = registry.get_config(arch)
    run_kw = {}
    for lv in levers:
        cfg, run_kw = LEVERS[lv](cfg, run_kw)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = mesh.devices.size
    sizes = SH.mesh_axis_sizes(mesh)
    batch_shards = int(np.prod([sizes.get(a, 1) for a in ("pod", "data")]))
    a_eff = 1
    if shape.kind == "train":
        a_cap = run_kw.pop("_grad_accum", GRAD_ACCUM.get(arch, 1))
        a_eff = max(1, min(a_cap, shape.global_batch // batch_shards))
    else:
        run_kw.pop("_grad_accum", None)
    grad_specs_flag = run_kw.get("_grad_specs", False)
    run = RunConfig(
        model=cfg, shape=shape, grad_accum=a_eff,
        **{k: v for k, v in run_kw.items() if k != "_grad_specs"},
    )
    object.__setattr__(run, "_grad_specs_flag", grad_specs_flag)
    pod_block = ndev // 2 if multi_pod else None

    # ---- full-config compile: proves sharding coherence + memory fit
    t0 = time.time()
    lowered, rules = build_lowered(cfg, shape, mesh, run)
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1
    mem = compiled.memory_analysis()
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "devices": ndev,
        "grad_accum": a_eff,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "rules": {
            "batch": rules.batch,
            "heads": None if rules.heads is None else "tp",
            "seq": rules.seq,
            "kv_seq": rules.kv_seq,
        },
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
        "raw_roofline_scanbody_once": roofline_from_compiled(
            compiled, num_devices=ndev, pod_block=pod_block
        ),
    }

    # ---- calibrated roofline (true per-step costs)
    if calibrate:
        total, detail = calibrated_costs(
            arch, cfg, shape, mesh, a_eff=a_eff, pod_block=pod_block,
            run_kw=run_kw,
        )
        hw = V5E
        t_compute = total["flops"] / hw.peak_bf16_flops
        t_memory = total["bytes"] / hw.hbm_bw
        t_coll = total["coll_intra"] / hw.ici_bw + total["coll_inter"] / hw.inter_pod_bw
        terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
        mf = model_flops_for(cfg, shape)
        bound = max(terms.values())
        rec["roofline"] = {
            "flops_per_device": total["flops"],
            "bytes_per_device": total["bytes"],
            "coll_intra_bytes": total["coll_intra"],
            "coll_inter_bytes": total["coll_inter"],
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": max(terms, key=terms.get),
            "bound_time_s": bound,
            "model_flops": mf,
            "useful_flops_ratio": mf / (total["flops"] * ndev)
            if total["flops"]
            else 0.0,
            "roofline_fraction": (mf / ndev / hw.peak_bf16_flops) / bound
            if bound > 0
            else 0.0,
            "calibration": detail,
        }
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--levers", default="", help="comma list: bf16mm,inscan,master,chunk4k")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    args = ap.parse_args()
    levers = tuple(x for x in args.levers.split(",") if x)

    archs = [args.arch] if args.arch else list(registry.ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    n_ok, failures = 0, []
    for arch in archs:
        for shape_name in shapes:
            ok, why = registry.cell_supported(arch, shape_name)
            if not ok:
                print(f"SKIP  {arch} × {shape_name}: {why}")
                continue
            for multi in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"CACHED {tag}")
                    n_ok += 1
                    continue
                print(f"RUN   {tag} ...", flush=True)
                try:
                    rec = lower_cell(
                        arch, shape_name, multi_pod=multi,
                        calibrate=not args.no_calibrate, levers=levers,
                    )
                    if levers:
                        rec["levers"] = list(levers)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    line = (
                        f"  OK compile={rec['compile_s']}s "
                        f"hbm={rec['memory_analysis']['total_bytes']/1e9:.2f}GB/dev"
                    )
                    if "roofline" in rec:
                        r = rec["roofline"]
                        line += (
                            f" dominant={r['dominant']}"
                            f" compute={r['t_compute_s']:.2e}s"
                            f" mem={r['t_memory_s']:.2e}s"
                            f" coll={r['t_collective_s']:.2e}s"
                            f" roofline_frac={r['roofline_fraction']:.3f}"
                        )
                    print(line, flush=True)
                    n_ok += 1
                except Exception as e:
                    failures.append((tag, repr(e)))
                    with open(os.path.join(args.out, tag + ".FAIL"), "w") as f:
                        f.write(traceback.format_exc())
                    print(f"  FAIL {e!r}", flush=True)
    print(f"\n{n_ok} ok, {len(failures)} failed")
    for tag, err in failures:
        print("  FAIL", tag, err[:160])
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
