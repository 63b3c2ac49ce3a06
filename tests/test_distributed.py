"""Distribution layer tests.

Sharding-rule unit tests run in-process (pure spec construction — no
devices); lowering tests run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=16 so the main pytest
process keeps its single-device view (per the dry-run isolation rule).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed.analysis import Roofline, collective_bytes
from repro.distributed.hlo_stats import (
    analyze,
    cross_edge_bytes,
    parse_computations,
    replica_groups_cross_block,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 4}


def test_param_specs_rules():
    from repro.distributed.sharding import param_specs
    from repro.launch.specs import param_shapes

    cfg = get_smoke_config("qwen3-14b")
    sds = param_shapes(cfg)
    specs = param_specs(cfg, sds, "tp", FakeMesh())
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_path = {"/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path): s
               for path, s in flat}
    wq = [v for k, v in by_path.items() if "wq" in k and k.endswith("w")]
    assert all(s[-1] == "model" for s in wq), wq  # column parallel
    wo = [v for k, v in by_path.items() if k.endswith("wo/w") and "blocks" in k]
    assert all(s[-2] == "model" for s in wo)  # row parallel
    norms = [v for k, v in by_path.items() if "norm" in k]
    assert all(all(x is None for x in s) for s in norms)  # replicated


def test_param_specs_divisibility_guard():
    """vocab 49155 % 4 != 0 -> embedding stays unsharded on vocab dim."""
    from repro.distributed.sharding import param_specs
    from repro.launch.specs import param_shapes

    cfg = get_smoke_config("granite-moe-3b-a800m")  # vocab 256 though; use full
    from repro.configs import get_config

    cfg = get_config("granite-moe-3b-a800m")
    sds = param_shapes(cfg)
    specs = param_specs(cfg, sds, "tp", FakeMesh())
    emb_spec = specs["embed"]["emb"]
    assert emb_spec[0] is None  # 49155 not divisible


def test_collective_bytes_parser():
    text = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[64]{0} all-reduce(%y), to_apply=%add
  %done = f32[64]{0} all-reduce-done(%ar.1)
"""
    out = collective_bytes(text)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 64 * 4


def test_hlo_stats_while_multiplier():
    text = """
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %a = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%p, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %init = (s32[], f32[8,8]) tuple(%x, %x)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    st = analyze(text)
    # one dot of 2*8*8*8 flops, executed 5 times
    assert st.flops == pytest.approx(5 * 2 * 8 * 8 * 8)
    assert st.whiles == [("body", 5)]


def test_replica_groups_cross_block():
    """The cross-edge classifier: a collective crosses edge blocks iff any
    replica group spans devices from more than one devs_per_block block."""
    # explicit groups
    assert not replica_groups_cross_block("{0,1},{2,3}", 2)
    assert replica_groups_cross_block("{0,2},{1,3}", 2)
    assert replica_groups_cross_block("{0,1,2,3}", 2)
    assert not replica_groups_cross_block("{0},{1},{2},{3}", 1)
    assert replica_groups_cross_block("{0,1}", 1)
    # iota form [n_groups,group_size]<=[n_devices]: contiguous blocks
    assert not replica_groups_cross_block("[4,2]<=[8]", 2)
    assert replica_groups_cross_block("[2,4]<=[8]", 2)
    assert not replica_groups_cross_block("[2,2]<=[4]", 4)  # sub-block groups
    # unknown format: conservative (counts as crossing)
    assert replica_groups_cross_block("", 2)


def test_cross_edge_bytes_classifier():
    """End-to-end on parsed HLO: only collectives whose groups span edge
    blocks count toward the cross-edge total."""
    text = """
ENTRY %main (x: f32[64]) -> f32[64] {
  %x = f32[64]{0} parameter(0)
  %a = f32[64]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %b = f32[64]{0} all-reduce(%a), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    st = analyze(text)
    # both collectives move 64*4 B; only the second crosses 2-device blocks
    assert cross_edge_bytes(st, 2) == pytest.approx(64 * 4)
    assert cross_edge_bytes(st, 1) == pytest.approx(2 * 64 * 4)
    assert cross_edge_bytes(st, 4) == pytest.approx(0.0)


def test_roofline_terms():
    r = Roofline(
        flops=197e12, bytes_accessed=819e9,
        coll_bytes={"all-reduce": 50e9, "all-gather": 25e9}, n_devices=256,
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    # 2x AR + 1x AG over 50 GB/s
    assert r.collective_s == pytest.approx((2 * 50e9 + 25e9) / 50e9)
    assert r.dominant == "collective"


@pytest.mark.slow
def test_smoke_lowering_on_16dev_mesh():
    """Subprocess: lower a smoke arch train step on a 4x4 host-device mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, dataclasses, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch.specs import param_shapes, train_batch_specs
from repro.distributed.sharding import param_specs, opt_state_specs
from repro.distributed.axes import auto_mesh, sharding_hints
from repro.models.config import InputShape
from repro.training.train_step import make_train_step, TrainState
from repro.training.optimizers import adam

mesh = auto_mesh((4, 4), ("data", "model"))
ok = {}
for arch in ["qwen3-14b", "dbrx-132b", "jamba-1.5-large-398b", "rwkv6-7b"]:
    cfg = dataclasses.replace(get_smoke_config(arch), remat=True)
    shape = InputShape("t", 64, 8, "train")
    opt = adam(1e-3)
    psds = param_shapes(cfg)
    pspec = param_specs(cfg, psds, "fsdp", mesh)
    ospec = opt_state_specs(pspec, jax.eval_shape(opt.init, psds), psds)
    sspec = TrainState(pspec, ospec, P())
    ssds = jax.eval_shape(lambda ps: TrainState(ps, opt.init(ps), jnp.zeros((), jnp.int32)), psds)
    bsds = train_batch_specs(cfg, shape)
    bspec = {k: P("data", None) if v.ndim == 2 else P("data", None, None) for k, v in bsds.items()}
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
    fn = make_train_step(cfg, opt)
    with mesh, sharding_hints(mesh):
        c = jax.jit(fn, in_shardings=(named(sspec), named(bspec)),
                    out_shardings=(named(sspec), None)).lower(ssds, bsds).compile()
    ok[arch] = c.memory_analysis().temp_size_in_bytes
print(json.dumps(ok))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res) == 4 and all(v > 0 for v in res.values())
