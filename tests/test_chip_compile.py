"""Compile rehearsals of the on-chip main path for a described TPU v5e.

Nothing here runs on a chip.  The TPU compiler, which is installed with
``libtpu``, compiles for a ``v5e:2x2`` topology that is described and not
attached, and refuses what the chip's compiler would refuse: an op it
cannot lower, a program that does not fit the device's memory.  Both
programs are compiled with the smoke's engine configuration at the
per-tile layout of ``chip_smoke.py --scale 22`` (its default scale is
smaller, to fit its time limit): Graph500 scale 22, edgefactor 16, T=16
tiles, edge shards declared in HBM.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in a ``parametrize``): only one process at a time may load
the TPU library, and every test worker imports this file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

import chip_smoke  # noqa: E402
from repro.core.algorithms import _local_call  # noqa: E402
from repro.core.engine import BFS, GraphShard  # noqa: E402
from repro.core.program import as_program  # noqa: E402
from repro.serve.lanes import local_lanes_call  # noqa: E402

SCALE = 22
T = 16
V_CHUNK = (1 << SCALE) // T
# a symmetrized edgefactor-16 graph holds at most 2 * 16 * V directed edges
E_CHUNK = 2 * chip_smoke.EDGE_FACTOR * (1 << SCALE) // T
HBM_BYTES = 16 * 10**9   # TPU v5e


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache but can never be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, lead=()):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    shard = GraphShard(sds((T, V_CHUNK), jnp.int32),
                       sds((T, V_CHUNK), jnp.int32),
                       sds((T, E_CHUNK), jnp.int32),
                       sds((T, E_CHUNK), jnp.float32))
    state = (sds(lead + (T, V_CHUNK), jnp.float32),
             sds(lead + (T, V_CHUNK), jnp.bool_),
             sds(lead + (T, V_CHUNK), jnp.float32))
    return shard, state


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_bfs_local_call_compiles_for_v5e(one_chip):
    cfg = chip_smoke.engine_config(T)
    shard, (value, frontier, acc) = _shapes(one_chip)
    compiled = _local_call.lower(as_program(BFS), cfg, T, E_CHUNK, V_CHUNK,
                                 shard, value, frontier, acc).compile()
    # the graph is resident at its real size: two (T, E_CHUNK) edge arrays
    assert compiled.memory_analysis().argument_size_in_bytes \
        >= 8 * T * E_CHUNK
    assert _device_bytes(compiled) < HBM_BYTES


def test_serving_lanes_compile_for_v5e(one_chip):
    cfg = chip_smoke.engine_config(T)
    shard, (value, frontier, acc) = _shapes(one_chip,
                                            lead=(chip_smoke.SERVE_WIDTH,))
    compiled = local_lanes_call.lower(as_program(BFS), cfg, T, E_CHUNK,
                                      V_CHUNK, shard, value, frontier,
                                      acc).compile()
    assert _device_bytes(compiled) < HBM_BYTES
