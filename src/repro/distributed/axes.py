"""Activation-sharding hints: with_sharding_constraint annotations for the
model's internals, configurable by the launcher.

Production JAX frameworks pin activation shardings at layer boundaries so the
SPMD partitioner cannot lose them inside scan/vmap autodiff residuals (we
observed exactly that: attention probabilities saved for backward reverting
to replicated batch — a 32x temp-memory blowup).  Models call
``constrain(x, kind)``; with no hints set (unit tests, CPU runs) it is the
identity.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingHints:
    batch_axes: Optional[Tuple[str, ...]] = None  # ('pod','data') / ('data',)
    model_axis: Optional[str] = None  # 'model'
    batch_size: int = 1  # product of batch axis sizes
    model_size: int = 1

    @property
    def batch(self):
        if not self.batch_axes:
            return None
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]


_HINTS = ShardingHints()


def current_hints() -> ShardingHints:
    return _HINTS


EDGE_AXIS = "edge"


def edge_mesh(n_devices: Optional[int] = None, *, devices=None):
    """1-D device mesh over the hierarchical-FL ``"edge"`` axis.

    The federation's topology maps edges onto mesh devices: edge ``j``
    lives on device ``j // (n_edges / n_devices)``, its EUs' cohort rows
    are co-located with it, and the only cross-device traffic is the cloud
    reduction (``MeshSyncEngine``).  ``n_devices=None`` takes every visible
    device; pass a smaller count to build a sub-mesh (the cross-mesh parity
    harness runs {1, 2, 4, 8} out of one 8-device process).  On CPU the
    devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    — virtual devices that share one thread pool, so the mesh path is a
    topology/accounting tool there, not a speedup.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    k = len(devs) if n_devices is None else int(n_devices)
    if k < 1 or k > len(devs):
        raise ValueError(
            f"edge_mesh needs 1 <= n_devices <= {len(devs)} visible devices, got {k}"
        )
    return Mesh(np.asarray(devs[:k]), (EDGE_AXIS,))


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axis types.  ``constrain`` pins layouts
    with ``with_sharding_constraint``, which ``Explicit`` axes (the
    ``make_mesh`` default) refuse."""
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@contextlib.contextmanager
def sharding_hints(mesh=None, *, batch_axes=None, model_axis="model"):
    """Derive hints from a mesh: batch axes = all non-model axes."""
    global _HINTS
    prev = _HINTS
    if mesh is not None:
        if batch_axes is None:
            batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)
        bs = 1
        for a in batch_axes:
            bs *= mesh.shape[a]
        ms = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1
    else:
        bs = ms = 1
    _HINTS = ShardingHints(
        tuple(batch_axes) if batch_axes else None,
        model_axis if mesh is not None else None,
        bs,
        ms,
    )
    try:
        yield _HINTS
    finally:
        _HINTS = prev


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_gate(x, dtype_name: str):
    return x


def _gate_fwd(x, dtype_name):
    return x, None


def _gate_bwd(dtype_name, _res, g):
    import jax.numpy as jnp

    return (g.astype(jnp.dtype(dtype_name)),)


_grad_gate.defvjp(_gate_fwd, _gate_bwd)


def grad_cast(x, dtype=None):
    """Identity in forward; casts the COTANGENT to ``dtype`` (default x.dtype)
    in backward.  Placed at sequence-parallel boundaries so the backward
    all-gather moves bf16, not the fp32 cotangents produced by
    preferred_element_type=f32 einsums (2x collective bytes otherwise)."""
    import jax.numpy as jnp

    d = jnp.dtype(dtype or x.dtype).name
    return _grad_gate(x, d)


def constrain(x, kind: str):
    """Annotate activation ``x`` with the canonical layout for ``kind``.

    kinds (batch dim must divide the batch axes to be constrained):
      tokens : (B, S, d)        -> P(batch, model, None)   [sequence parallel]
      heads  : (B, S, H, Dh)    -> P(batch, None, model, None)
      probs  : (B, H, q, k)     -> P(batch, model, None, None)
      inner  : (B, S, d_inner)  -> P(batch, None, model)
      ssm    : (B, S, di, n)    -> P(batch, None, model, None)
      rwkv5  : (B, H, C, C, hs) -> P(batch, model, None, None, None)
      dispatch: (g, tg, E, C)   -> P(batch, None, model, None)
      experts : (g, E, C, d)    -> P(batch, model, None, None)
      state  : (B, H|d_inner, ...) -> P(batch, model, ...)
    """
    h = _HINTS
    if h.batch_axes is None and h.model_axis is None:
        return x
    m = h.model_axis
    nd = x.ndim
    b = h.batch if (h.batch and x.shape[0] % h.batch_size == 0 and x.shape[0] >= h.batch_size) else None

    def mod(dim):
        return m if (m and x.shape[dim] % h.model_size == 0 and x.shape[dim] >= h.model_size) else None

    if kind == "tokens" and nd == 3:
        # sequence-parallel layout between layers: residual stream sharded
        # over (batch, seq) — remat-saved block inputs shrink by model_size.
        spec = P(b, mod(1), None)
    elif kind == "heads" and nd == 4:
        spec = P(b, None, mod(2), None)
    elif kind == "probs" and nd == 4:
        spec = P(b, mod(1), None, None)
    elif kind == "inner" and nd == 3:
        spec = P(b, None, mod(2))
    elif kind == "ssm" and nd == 4:
        spec = P(b, None, mod(2), None)
    elif kind == "rwkv5" and nd == 5:
        spec = P(b, mod(1), None, None, None)
    elif kind == "kvlogits" and nd == 4:  # (B, H, q, S): seq-sharded scores
        spec = P(b, None, None, mod(3))
    elif kind == "dispatch" and nd == 4:  # (g, tg, E, C)
        spec = P(b, None, mod(2), None)
    elif kind == "experts" and nd == 4:  # (g, E, C, d|f)
        spec = P(b, mod(1), None, None)
    elif kind == "state" and nd >= 2:
        spec = P(b, mod(1), *([None] * (nd - 2)))
    else:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
