"""Compile rehearsals for the TPU v5e: the main path's aggregation kernels,
compiled with ``interpret=False`` at the paper CNN's flat width against a
described ``v5e:2x2`` topology.  Nothing runs; a compile that passes here is
one the chip's compiler accepts (VMEM budget, tiling), at no chip time.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler library.  Keep every
such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hier_aggregate import hier_aggregate
from repro.kernels.segment_aggregate import hier_segment_aggregate

D = 25_141  # HEARTBEAT_CNN's flat parameter count


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from a persistent
    # cache, so keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,e", [(18, 5), (300, 5), (2048, 8)])
def test_segment_aggregate_compiles_for_v5e(one_chip, n, e):
    text = _compiled_text(
        lambda u, s, w: hier_segment_aggregate(u, s, w, e, interpret=False), one_chip,
        ((n, D), jnp.float32), ((n,), jnp.int32), ((n,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [18, 512, 2048])
def test_hier_aggregate_compiles_for_v5e(one_chip, n):
    text = _compiled_text(
        lambda u, w: hier_aggregate(u, w, interpret=False), one_chip,
        ((n, D), jnp.float32), ((n,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_eval_program_compiles_for_v5e(one_chip):
    """The test-set evaluation at the paper's size (1,500 rows of 187 x 1,
    batches of 512): one program, its full batches in one loop."""
    from repro.federated.programs import CNNProgram
    from repro.federated.simulation import _eval_batches
    from repro.models.cnn1d import HEARTBEAT_CNN

    program = CNNProgram(HEARTBEAT_CNN)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(program.init, jax.random.PRNGKey(0)),
    )
    x = jax.ShapeDtypeStruct((1_500, 187, 1), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((1_500,), jnp.int32, sharding=one_chip)
    compiled = _eval_batches.lower(params, x, y, program, 512).compile()
    assert compiled.out_info.shape == (3,)
    assert "while" in compiled.as_text()
