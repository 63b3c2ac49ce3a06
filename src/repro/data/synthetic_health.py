"""Synthetic ECG/EEG-like datasets matching the paper's class structure.

MIT-BIH Heartbeat and the AUBMC Seizure recordings are not available offline;
we synthesize separable-but-noisy 1-D signals whose *class-count structure*
matches the paper exactly (Tables 2-3).  Each class is a distinct mixture of
sinusoids + transient spikes so that a small CNN can reach high accuracy and
imbalance effects mirror the real experiments (see DESIGN.md Sec. 8).

Heartbeat: 5 classes, 1 channel, length 187 (kaggle segmented ECG format).
Seizure:   3 classes, 19 channels (10-20 electrode montage), length 178.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass
class Dataset:
    x: np.ndarray  # (N, L, C) float32
    y: np.ndarray  # (N,) int32
    n_classes: int

    def subset(self, idx) -> "Dataset":
        return Dataset(self.x[idx], self.y[idx], self.n_classes)

    def __len__(self):
        return len(self.y)


# clients synthesised together by SignalBank.write: bounds its scratch to a
# few MB at the streaming shard sizes
_BLOCK_CLIENTS = 16


class SignalBank:
    """The synthetic signals of one layout: per-class constants and a
    batched writer.

    Each class is a distinct morphology: a base frequency and a
    class-specific transient (QRS-like for ECG / spike-wave for EEG), mixed
    across channels, plus noise.  Sample ``i`` of class ``c`` is::

        amp_i * sin(2*pi*f_c*t + phase_i) + spike_c   (each channel times chan_mix_c)
        + 0.35 * z_i                                  (z_i standard normal per element)

    with ``phase_i`` uniform in ``[0, 2*pi)`` and ``amp_i`` in ``[0.8, 1.2)``,
    all in float32.  The time axis, the phase ramp, the scaled transient
    and the channel mix depend only on ``(class, length, channels)``, so
    they are computed once here.
    """

    def __init__(self, n_classes: int, length: int, channels: int):
        self.length, self.channels = int(length), int(channels)
        t = np.linspace(0, 1, length, dtype=np.float32)
        ramp, spike, chan_mix = [], [], []
        for cls in range(n_classes):
            base_freq = 2.0 + 3.0 * cls
            ramp.append(2 * np.pi * base_freq * t)
            center = int(length * (0.2 + 0.15 * cls))
            width = max(3, length // 40)
            s = np.exp(-0.5 * ((np.arange(length) - center) / width) ** 2).astype(np.float32)
            spike.append((1.5 + 0.5 * cls) * s)
            chan_mix.append(
                (1.0 + 0.3 * np.sin(np.arange(channels) * (cls + 1))).astype(np.float32)
            )
        self._ramp = np.stack(ramp)[:, :, None]  # (K, L, 1)
        self._spike = np.stack(spike)[:, :, None]  # (K, L, 1)
        self._chan_mix = np.stack(chan_mix)  # (K, Ch)

    def write(self, rngs, class_counts, x: np.ndarray, y: np.ndarray) -> None:
        """Synthesise client ``k``'s samples into ``x[k, :n_k]`` (n_k, L, Ch)
        and ``y[k, :n_k]``, drawing from ``rngs[k]``; ``class_counts`` is
        ``(R, K)`` and ``n_k`` the sum of row ``k``'s positive counts.
        Samples past ``n_k`` are left as they are.

        Each client draws, class by class, ``2 c`` uniforms (the ``c``
        phases', then the ``c`` amplitudes') and ``c * L * Ch`` standard
        normals, then ``rng.permutation(n_k)`` orders its samples.  That is
        the stream ``rng.uniform(0, 2*pi, c)``, ``rng.random(c)``,
        ``rng.normal(0, 0.35, (c, L, Ch))`` per class consumes, and the
        arithmetic below is theirs (``0 + 2*pi*u``, ``0.0 + 0.35*z``), so
        the bytes are those of drawing each class with those calls.  The
        arithmetic runs vectorised over blocks of clients; only the draws
        are per client.
        """
        counts = np.clip(np.asarray(class_counts, dtype=np.int64), 0, None)
        for a in range(0, len(counts), _BLOCK_CLIENTS):
            b = a + _BLOCK_CLIENTS
            self._write_block(rngs[a:b], counts[a:b], x[a:b], y[a:b])

    def _write_block(self, rngs, counts: np.ndarray, x: np.ndarray, y: np.ndarray) -> None:
        L, Ch = self.length, self.channels
        r, k = counts.shape
        n = counts.sum(axis=1)
        start = np.zeros(r + 1, np.int64)
        np.cumsum(n, out=start[1:])
        s = int(start[-1])
        u = np.empty(2 * s)
        z = np.empty((s, L, Ch))
        # drawn sample -> its row and position in x
        row = np.repeat(np.arange(r), n)
        col = np.empty(s, np.int64)
        for i, (rng, cnts) in enumerate(zip(rngs, counts.tolist())):
            j = int(start[i])
            for c in cnts:
                if c:
                    rng.random(out=u[2 * j : 2 * (j + c)])
                    rng.standard_normal(out=z[j : j + c])
                    j += c
            # position p holds drawn sample perm[p]
            col[start[i] + rng.permutation(int(n[i]))] = np.arange(n[i])
        flat = counts.ravel()
        lab = np.repeat(np.tile(np.arange(k), r), flat)
        first = np.repeat(np.cumsum(flat) - flat, flat)  # its (client, class) block's first sample
        off = np.arange(s) - first
        phase = (2 * np.pi * u[2 * first + off]).astype(np.float32)
        amp = (0.8 + 0.4 * u[2 * first + np.repeat(flat, flat) + off]).astype(np.float32)
        noise = np.empty((s, L, Ch), np.float32)
        np.multiply(z, 0.35, out=z)
        np.add(z, 0.0, out=noise)
        del z  # the float64 draws are the block's largest buffer
        sig = amp[:, None, None] * np.sin(self._ramp[lab] + phase[:, None, None])
        sig += self._spike[lab]
        noise += sig * self._chan_mix[lab][:, None, :]
        x[row, col] = noise
        y[row, col] = lab

    def dataset(self, rng, class_counts) -> Dataset:
        counts = np.asarray(class_counts, dtype=int)
        n = int(counts[counts > 0].sum())
        if n == 0:
            raise ValueError("class_counts hold no positive count: no sample to make")
        x = np.empty((1, n, self.length, self.channels), np.float32)
        y = np.empty((1, n), np.int32)
        self.write([rng], counts[None], x, y)
        return Dataset(x[0], y[0], n_classes=len(counts))


def make_dataset(
    rng: np.random.Generator,
    class_counts: np.ndarray,
    length: int,
    channels: int,
) -> Dataset:
    return SignalBank(len(class_counts), length, channels).dataset(rng, class_counts)


def heartbeat_like(rng, class_counts) -> Dataset:
    return make_dataset(rng, class_counts, length=187, channels=1)


def seizure_like(rng, class_counts) -> Dataset:
    return make_dataset(rng, class_counts, length=178, channels=19)
