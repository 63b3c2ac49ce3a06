"""Mesh builders for the production TPU v5e topology.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so 512 host placeholder devices exist.

``make_hfl_mesh`` factors the data axis into (edge, eu) for the paper's
hierarchical-FL-on-mesh mapping (DESIGN.md Sec. 3): edge aggregation reduces
over ``eu`` only; cloud aggregation reduces over (``pod``, ``edge``).
"""
from __future__ import annotations

from repro.distributed.axes import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_hfl_mesh(*, multi_pod: bool = False, n_edges: int = 4):
    """(pod,) edge x eu x model factorization of the production mesh."""
    if multi_pod:
        assert 16 % n_edges == 0
        return auto_mesh((2, n_edges, 16 // n_edges, 16), ("pod", "edge", "eu", "model"))
    assert 16 % n_edges == 0
    return auto_mesh((n_edges, 16 // n_edges, 16), ("edge", "eu", "model"))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CPU debugging (requires >= n_data*n_model host devices)."""
    if multi_pod:
        return auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))
