"""Operation and byte counts, by hand."""
import json
from pathlib import Path

import pytest

import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _widths(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["widths"]


def test_heartbeat_forward_flops_by_hand():
    # conv1 187*5*1*16, conv2 93*5*16*16, fc1 (46*16)*32, fc2 32*5 multiply-adds
    macs = 187 * 5 * 16 + 93 * 5 * 16 * 16 + 736 * 32 + 32 * 5
    assert flops.cnn_forward_flops(_widths("cnn-heartbeat")) == 2 * macs == 315_424
    assert flops.cnn_train_flops(_widths("cnn-heartbeat")) == 3 * 315_424


@pytest.mark.parametrize("name", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_config_parameter_count(name):
    from reference import CNN

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    w = cfg["widths"]
    cnn = CNN(w["in_channels"], w["n_classes"], w["seq_len"], w["c1"], w["c2"], w["hidden"],
              w["kernel"])
    assert cnn.n_params() == cfg["n_params"]


def test_segment_mean_least_is_memory_bound_at_paper_shape():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops.segment_mean_least(18, 5, 25_141, peaks)
    assert least["ops"] == 18 * 25_141
    assert least["bytes"] == 4 * (18 + 10) * 25_141
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(4 * 28 * 25_141 / 819e9)
