"""Dry-run machinery on a small fake-device mesh (subprocess — the main
process keeps 1 device).  Exercises lower+compile+cost extraction for one
train and one decode cell on a (2,2,2) pod/data/model mesh with smoke
configs, plus collective-byte parsing and hierarchical psum."""

import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.configs import registry
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch import sharding as SH
from repro.models.common import AxisRules
from repro.roofline.analysis import collective_bytes, roofline_from_compiled
from repro.train.train_step import make_train_step, init_train_state

mesh = compat.make_mesh((2,2,2), ("pod","data","model"))
cfg = registry.get_config("minitron-4b", smoke=True)
shape = ShapeConfig("t", 32, 8, "train")
rules = SH.rules_for(cfg, shape, mesh)
api = registry.get_model_api(cfg)
run = RunConfig(model=cfg, shape=shape, grad_accum=2)
key = jax.random.PRNGKey(0)
from repro.optim.adamw import adamw_init
state_shape = jax.eval_shape(lambda: {"params": api.init(key,cfg), "opt": adamw_init(api.init(key,cfg)), "step": jnp.zeros((),jnp.int32)})
pspecs = SH.sanitize_specs(api.param_specs(cfg, rules, 2), jax.eval_shape(lambda: api.init(key,cfg)), mesh)
sspecs = {"params": pspecs, "opt": {"m": pspecs, "v": pspecs, "count": P()}, "step": P()}
in_specs = registry.input_specs(cfg, shape)
bspecs = SH.sanitize_specs(SH.batch_specs(cfg, shape, rules), in_specs, mesh)
with jax.set_mesh(mesh):
    step = make_train_step(cfg, run, api, rules)
    jitted = jax.jit(step, in_shardings=(SH.named(sspecs,mesh), SH.named(bspecs,mesh)),
                     out_shardings=(SH.named(sspecs,mesh), None), donate_argnums=(0,))
    lowered = jitted.lower(state_shape, in_specs)
    compiled = lowered.compile()
    r = roofline_from_compiled(compiled, num_devices=8, pod_block=4)
    assert r["flops_per_device"] > 0
    assert r["collective_bytes"]["total"] > 0, "sharded train step must communicate"
    assert r["memory_analysis"]["total_bytes"] > 0
    print("train cell ok; dominant:", r["dominant"], "coll inter:", r["collective_bytes"]["inter_pod"])

# hierarchical psum: inter-pod bytes must drop vs flat psum
from repro.runtime.collectives import hierarchical_psum
def flat(x): return jax.lax.psum(x, ("data","pod"))
def hier(x): return hierarchical_psum(x, fast_axis="data", slow_axis="pod")
xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
cb = {}
for name, fn in [("flat", flat), ("hier", hier)]:
    f = compat.shard_map(fn, mesh=mesh, in_specs=P(None, "model"), out_specs=P(None, "model"))
    comp = jax.jit(f).lower(xs).compile()
    cb[name] = collective_bytes(comp.as_text(), num_devices=8, pod_block=4)
print("flat inter:", cb["flat"]["inter_pod"], "hier inter:", cb["hier"]["inter_pod"])
assert cb["hier"]["inter_pod"] < cb["flat"]["inter_pod"] or cb["flat"]["inter_pod"] == 0
print("DRYRUN_SMALL_OK")
"""


@pytest.mark.slow
def test_dryrun_small_mesh():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd="/root/repo",
    )
    assert "DRYRUN_SMALL_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
