"""Cross-implementation consistency oracles:

* prefill+decode == full forward (teacher forcing), all families
* chunked mamba/rwkv == naive step recurrence
* grouped MoE == dense MoE (ample capacity)
* blockwise attention == full-softmax sdpa
* golden-trajectory pins: the CNN 2-round HFL trajectory on all three
  engine paths must reproduce committed param hashes bit for bit
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import forward, init_params
from repro.models.attention import blockwise_attention, causal_mask, sdpa
from repro.models.mamba import mamba_decode_step, mamba_init, mamba_init_state, mamba_mixer
from repro.models.moe import moe_init, moe_mlp, moe_mlp_grouped, moe_mlp_sparse
from repro.models.rwkv import rwkv_decode_step, rwkv_init, rwkv_init_state, rwkv_mixer
from repro.models.transformer import decode_step, prefill

FAMS = ["qwen3-14b", "starcoder2-3b", "dbrx-132b", "jamba-1.5-large-398b", "rwkv6-7b", "whisper-tiny"]


@pytest.mark.parametrize("arch", FAMS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    kw = {}
    if cfg.family == "encdec":
        kw["enc_embeds"] = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.n_audio_frames, cfg.d_model))
    full, _ = forward(params, cfg, toks, **kw)
    pl_, cache = prefill(params, cfg, toks[:, :S], max_seq=S + 4, **kw)
    assert float(jnp.max(jnp.abs(pl_[:, 0] - full[:, S - 1]))) < 1e-4
    dl, _ = decode_step(params, cfg, toks[:, S:S + 1], cache, jnp.full((B,), S, jnp.int32))
    assert float(jnp.max(jnp.abs(dl[:, 0] - full[:, S]))) < 1e-4


def test_mamba_chunked_equals_step():
    cfg = get_smoke_config("jamba-1.5-large-398b")
    p = mamba_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 29, cfg.d_model)) * 0.5
    y_chunk = mamba_mixer(p, cfg, x, chunk=8)
    st = mamba_init_state(cfg, 2)
    ys = []
    for t in range(29):
        yt, st = mamba_decode_step(p, cfg, x[:, t:t + 1], st)
        ys.append(yt)
    y_step = jnp.concatenate(ys, 1)
    assert float(jnp.max(jnp.abs(y_chunk - y_step))) < 1e-4


def test_rwkv_chunked_equals_step():
    cfg = get_smoke_config("rwkv6-7b")
    p = rwkv_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 23, cfg.d_model)) * 0.5
    y_chunk = rwkv_mixer(p, cfg, x, chunk=8)
    st = rwkv_init_state(cfg, 2)
    ys = []
    for t in range(23):
        yt, st = rwkv_decode_step(p, cfg, x[:, t:t + 1], st)
        ys.append(yt)
    y_step = jnp.concatenate(ys, 1)
    assert float(jnp.max(jnp.abs(y_chunk - y_step))) < 1e-4


def test_moe_grouped_equals_dense_with_ample_capacity():
    cfg = get_smoke_config("dbrx-132b")
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 0.5
    y_d, _, _ = moe_mlp(p, cfg, x)
    y_g, _, _ = moe_mlp_grouped(p, cfg, x, capacity_factor=8.0, group_size=64)
    assert float(jnp.max(jnp.abs(y_d - y_g))) < 1e-4


def test_moe_sparse_equals_dense():
    cfg = get_smoke_config("granite-moe-3b-a800m")
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model)) * 0.5
    y_d, _, _ = moe_mlp(p, cfg, x)
    y_s = moe_mlp_sparse(p, cfg, x)
    assert float(jnp.max(jnp.abs(y_d - y_s))) < 1e-4


@pytest.mark.parametrize("window", [None, 32])
def test_blockwise_attention_equals_sdpa(window):
    B, S, H, D = 2, 128, 4, 32
    ks = [jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D)) for i in range(3)]
    o1 = blockwise_attention(*ks, causal=True, window=window, q_block=32, kv_block=32)
    o2 = sdpa(*ks, causal_mask(S, S, window))
    assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-4


# -- golden trajectory pins (ISSUE 5) ----------------------------------------
_GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden", "cnn_trajectory.json")


def _params_hash(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def golden():
    with open(_GOLDEN_PATH) as f:
        data = json.load(f)
    if data["jax"] != jax.__version__ or data["backend"] != jax.default_backend():
        pytest.skip(
            f"golden pins recorded on jax {data['jax']}/{data['backend']}, "
            f"running {jax.__version__}/{jax.default_backend()} — regenerate "
            "with tools/golden_trajectory.py to pin this environment"
        )
    return data


@pytest.fixture(scope="module")
def golden_runs():
    from tools.golden_trajectory import golden_runs as _runs

    return _runs()


@pytest.mark.parametrize("path", ["sync-device", "reference", "async", "stream"])
def test_golden_cnn_trajectory_pinned(golden, golden_runs, path):
    """Refactors must not silently drift the reference CNN trajectories:
    final params hash (bit-exact) and the accuracy history are pinned to
    the committed values.  On drift: if the change is INTENTIONAL, rerun
    ``PYTHONPATH=src python tools/golden_trajectory.py`` and explain the
    new semantics in the PR; otherwise the refactor broke parity."""
    res = golden_runs[path]
    want = golden["runs"][path]
    assert [round(m.test_acc, 10) for m in res.history] == want["accs"]
    assert _params_hash(res.final_params) == want["params_sha256"]


def test_moe_dropped_tokens_get_zero_output():
    """Capacity overflow drops tokens (output zero for the dropped slots)."""
    cfg = get_smoke_config("dbrx-132b")
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    y_tight, _, _ = moe_mlp_grouped(p, cfg, x, capacity_factor=0.25, group_size=64)
    y_ample, _, _ = moe_mlp_grouped(p, cfg, x, capacity_factor=8.0, group_size=64)
    # tight capacity must differ (tokens dropped) but stay finite
    assert bool(jnp.all(jnp.isfinite(y_tight)))
    assert float(jnp.max(jnp.abs(y_tight - y_ample))) > 1e-6
