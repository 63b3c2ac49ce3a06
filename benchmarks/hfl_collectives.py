"""Hierarchical-FL-on-mesh communication claim (DESIGN.md Sec. 3).

Lowers, on a small host-device mesh, (a) the standard data-parallel train
step and (b) the HFL local + sync steps, and compares cross-edge collective
bytes per step: the amortized HFL schedule moves cross-edge bytes only every
T-th step — the paper's 75-85% round reduction, structurally.

Runs in a CPU-only subprocess (``JAX_PLATFORMS=cpu``) so the main process
keeps its own devices, including an accelerator it may hold.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch.specs import param_shapes, train_batch_specs
from repro.distributed.sharding import param_specs, opt_state_specs
from repro.distributed.axes import auto_mesh, sharding_hints
from repro.distributed.hfl_mesh import (
    hfl_batch_spec, hfl_param_specs, make_hfl_train_step, init_hfl_state,
)
from repro.distributed.hlo_stats import analyze, cross_edge_bytes
from repro.models.config import InputShape
from repro.training.train_step import TrainState, make_train_step
from repro.training.optimizers import adam

cfg = dataclasses.replace(get_smoke_config("phi3-mini-3.8b"), remat=True)
opt = adam(1e-3)
E, B_e, S = 4, 8, 64


def coll_of(lowered, devs_per_edge=None):
    st = analyze(lowered.compile().as_text())
    out = dict(st.coll_bytes)
    if devs_per_edge:
        out["_cross_edge"] = cross_edge_bytes(st, devs_per_edge)
    return out

out = {}
# (a) plain data parallel on (data=8, model=2)
mesh = auto_mesh((8, 2), ("data", "model"))
psds = param_shapes(cfg)
pspec = param_specs(cfg, psds, "tp", mesh)
ospec = opt_state_specs(pspec, jax.eval_shape(opt.init, psds), psds)
sspec = TrainState(pspec, ospec, P())
ssds = jax.eval_shape(lambda ps: TrainState(ps, opt.init(ps), jnp.zeros((), jnp.int32)), psds)
shape = InputShape("t", S, E * B_e, "train")
bsds = train_batch_specs(cfg, shape)
bspec = {k: P("data", None) for k in bsds}
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
with mesh, sharding_hints(mesh):
    low = jax.jit(make_train_step(cfg, opt), in_shardings=(named(sspec), named(bspec)),
                  out_shardings=(named(sspec), None)).lower(ssds, bsds)
out["dp"] = coll_of(low, devs_per_edge=4)  # data=8,model=2: 'edge block'=4 devs

# (b) HFL on (edge=4, eu=2, model=2)
mesh = auto_mesh((4, 2, 2), ("edge", "eu", "model"))
pspec_e = hfl_param_specs(param_specs(cfg, psds, "tp", mesh), ("edge",))
st_sds = jax.eval_shape(lambda ps: init_hfl_state(ps, opt, E), psds)
opt_spec_e = (jax.tree.map(lambda s: s, pspec_e), jax.tree.map(lambda s: s, pspec_e))
sspec_e = TrainState(pspec_e, opt_spec_e, P())
bspec_e = {k: hfl_batch_spec(("edge",), ("eu",)) for k in bsds}
bsds_e = {k: jax.ShapeDtypeStruct((E, B_e, S), v.dtype) for k, v in bsds.items()}
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
for tag, sync in (("hfl_local", False), ("hfl_sync", True)):
    step = make_hfl_train_step(cfg, opt, sync=sync)
    # inside the vmapped per-edge fn the batch dim is per-edge: hint 'eu' only
    with mesh, sharding_hints(mesh, batch_axes=("eu",)):
        low = jax.jit(step, in_shardings=(named(sspec_e), named(bspec_e)),
                      out_shardings=(named(sspec_e), None)).lower(st_sds, bsds_e)
    out[tag] = coll_of(low, devs_per_edge=4)  # eu*model = 4 devices per edge
print(json.dumps(out))
"""


def main() -> None:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child is a CPU accounting tool on virtual devices: it never
    # contends with a parent that holds an accelerator
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=1500)
    if res.returncode != 0:
        emit("hfl_collectives", 0.0, "FAILED: " + res.stderr.strip().splitlines()[-1][:120])
        raise RuntimeError(f"hfl_collectives child failed:\n{res.stderr[-3000:]}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    tot = {k: sum(v2 for k2, v2 in v.items() if k2 != "_cross_edge") for k, v in data.items()}
    xe = {k: v.get("_cross_edge", 0.0) for k, v in data.items()}
    for k in tot:
        emit(f"hfl_coll_bytes_{k}", 0.0,
             f"total={tot[k]:.3e} cross_edge={xe[k]:.3e} B/step")
    for t in (4, 8, 16):
        amort = ((t - 1) * xe["hfl_local"] + xe["hfl_sync"]) / t
        red = 100 * (1 - amort / max(xe["dp"], 1))
        emit(f"hfl_amortized_T{t}", 0.0,
             f"cross-edge {amort:.3e} B/step vs dp {xe['dp']:.3e} -> reduction {red:.0f}%")


if __name__ == "__main__":
    main()
