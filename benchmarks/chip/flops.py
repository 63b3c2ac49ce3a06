"""Operations and bytes that the algorithm needs, counted from shapes.

Kept with the benchmark so that every change is judged on the same work:
a reimplementation that does more arithmetic (a one-hot matmul for a
segment sum, padded rows) gets no credit for it.
"""
from __future__ import annotations


def cnn_forward_flops(cfg: dict) -> int:
    """Multiply-adds x2 of one sample's forward pass through the paper's CNN:
    two 'same' convolutions at full length (each followed by a pool that
    halves the length), then two dense layers.  Bias adds, relus and pools
    are left out; they are a fraction of a percent here."""
    k, cin, c1, c2 = cfg["kernel"], cfg["in_channels"], cfg["c1"], cfg["c2"]
    l1 = cfg["seq_len"]
    l2 = l1 // 2
    flat = (l2 // 2) * c2
    return 2 * (l1 * k * cin * c1 + l2 * k * c1 * c2 + flat * cfg["hidden"]
                + cfg["hidden"] * cfg["n_classes"])


def cnn_train_flops(cfg: dict) -> int:
    """Forward plus backward of one training sample: three forwards."""
    return 3 * cnn_forward_flops(cfg)


def segment_mean_least(n_rows: int, n_segments: int, dim: int, peaks: dict) -> dict:
    """Least time of a weighted segment mean of an (N, D) float32 matrix
    into (E, D): N*D multiply-adds counted as N*D operations, and the bytes
    of reading the rows once plus reading and writing the (E, D) output
    (the engines keep the previous model of an empty edge)."""
    ops = n_rows * dim
    nbytes = 4 * (n_rows * dim + 2 * n_segments * dim)
    t_ops = ops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return {"ops": ops, "bytes": nbytes, "seconds": max(t_ops, t_mem),
            "bound": "memory" if t_mem >= t_ops else "compute"}
