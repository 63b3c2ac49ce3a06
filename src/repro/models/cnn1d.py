"""The paper's client model: a small 1-D CNN classifier (~14.8k params).

"For the Heartbeat dataset, we use the model presented in [40], which expects
1 input channel and outputs probabilities for 5 classes. For the Seizure
dataset ... adapted to accommodate the 19 input channels and the 3 output
classes."  Fig. 6 states 14,789 parameters at 4 bytes each.

Architecture (matching the eddymina ECG reference net in spirit):
conv(k=5) -> relu -> maxpool2 -> conv(k=5) -> relu -> maxpool2 -> flatten ->
dense(32) -> relu -> dense(n_classes).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    in_channels: int = 1
    n_classes: int = 5
    seq_len: int = 187  # heartbeat dataset sample length
    c1: int = 16
    c2: int = 16
    hidden: int = 32
    kernel: int = 5

    @property
    def flat_dim(self) -> int:
        l1 = self.seq_len // 2
        l2 = l1 // 2
        return l2 * self.c2


HEARTBEAT_CNN = CNNConfig(in_channels=1, n_classes=5, seq_len=187)
SEIZURE_CNN = CNNConfig(in_channels=19, n_classes=3, seq_len=178)


def cnn_init(key, cfg: CNNConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv_w(k, cin, cout):
        scale = 1.0 / np.sqrt(cfg.kernel * cin)
        return jax.random.normal(k, (cfg.kernel, cin, cout), jnp.float32) * scale

    def lin_w(k, din, dout):
        return jax.random.normal(k, (din, dout), jnp.float32) / np.sqrt(din)

    return {
        "conv1": {"w": conv_w(k1, cfg.in_channels, cfg.c1), "b": jnp.zeros((cfg.c1,))},
        "conv2": {"w": conv_w(k2, cfg.c1, cfg.c2), "b": jnp.zeros((cfg.c2,))},
        "fc1": {"w": lin_w(k3, cfg.flat_dim, cfg.hidden), "b": jnp.zeros((cfg.hidden,))},
        "fc2": {"w": lin_w(k4, cfg.hidden, cfg.n_classes), "b": jnp.zeros((cfg.n_classes,))},
    }


def _conv1d_same(x, w, b):
    """x: (B, L, Cin); w: (K, Cin, Cout) 'same' padding."""
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    return out + b


def _maxpool2(x):
    l = x.shape[1] - (x.shape[1] % 2)
    # a static lax slice: eager ``x[:, :l]`` uploads its bounds as scalars
    x = jax.lax.slice_in_dim(x, 0, l, axis=1)
    return jnp.max(x.reshape(x.shape[0], l // 2, 2, x.shape[2]), axis=2)


def _conv1d_same_gemm(x, w):
    """Same contraction as :func:`_conv1d_same` (bias excluded), phrased as
    window-concat + one GEMM: (B, L, K*Cin) @ (K*Cin, Cout).

    ``lax.conv_general_dilated`` vmapped over per-client kernels lowers to a
    C-group convolution, which XLA:CPU executes as a serial per-group loop —
    the dominant cost of the batched cohort step.  The GEMM form lowers to
    one batched matmul instead (~1.7x faster cohort epochs at C=512 on CPU)
    and is numerically identical on the tested shapes (same K*Cin-ordered
    accumulation).
    """
    k, cin, cout = w.shape
    l = x.shape[1]
    pad_l = (k - 1) // 2
    xp = jnp.pad(x, ((0, 0), (pad_l, k - 1 - pad_l), (0, 0)))
    win = jnp.concatenate([xp[:, j : j + l] for j in range(k)], axis=-1)
    return win @ w.reshape(k * cin, cout)


def cnn_apply(params, cfg: CNNConfig, x, *, conv_impl: str = "xla"):
    """x: (B, L, Cin) float32 -> logits (B, n_classes).

    ``conv_impl``: "xla" — ``lax.conv_general_dilated`` (single-model path);
    "gemm" — window-concat matmuls, the formulation the vmapped cohort step
    uses so per-client convolutions become batched GEMMs.  The gemm path
    also pools BEFORE the bias+relu — exact (max commutes with the
    monotone bias-add and relu), and the elementwise work runs on the
    half-length tensor.
    """
    if conv_impl == "gemm":
        h = _maxpool2(_conv1d_same_gemm(x, params["conv1"]["w"]))
        h = jax.nn.relu(h + params["conv1"]["b"])
        h = _maxpool2(_conv1d_same_gemm(h, params["conv2"]["w"]))
        h = jax.nn.relu(h + params["conv2"]["b"])
    else:
        h = jax.nn.relu(_conv1d_same(x, params["conv1"]["w"], params["conv1"]["b"]))
        h = _maxpool2(h)
        h = jax.nn.relu(_conv1d_same(h, params["conv2"]["w"], params["conv2"]["b"]))
        h = _maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]
