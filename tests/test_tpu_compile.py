"""Compile the main path's kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler that ships with JAX compiles for a v5e:2x2
topology that is described, not attached, and refuses what the chip would
refuse (unaligned slices, too much VMEM, unlowerable ops).  ``interpret=
False`` is passed explicitly, so these tests do not depend on the
``INTERPRET`` flag the CPU test path decides at import.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.circuits import build_threshold_circuit
from repro.query import Interval, Threshold
from repro.query.compile import build_query_circuit

N_STORES = 64
N_WORDS = 1 << 22  # 2**27 rows per column


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "queries",
    [
        (Interval(2, 10),),
        (Threshold(3), Threshold(8), Interval(2, 10)),
    ],
    ids=["interval_2_10", "three_outputs"],
)
def test_fused_kernel_compiles_at_real_width(one_chip, queries):
    from repro.kernels.threshold_ssum import run_circuit_pallas

    names = tuple(f"s{i}" for i in range(N_STORES))
    circuit = build_query_circuit(queries, N_STORES, names)
    cols = jax.ShapeDtypeStruct((N_STORES, N_WORDS), jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        lambda b: run_circuit_pallas(b, circuit, interpret=False), cols
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "tile_words, group_tiles, k_max",
    [(64, 1 << 16, 1), (64, 1 << 16, 3), (8, 4, 1)],
    ids=["tw64_lanes1024", "tw64_three_outputs", "tw8_lanes32"],
)
def test_tiled_block_grid_compiles(one_chip, tile_words, group_tiles, k_max):
    """The scan engine's scalar-prefetch block grid, with a ``lax.switch``
    over residual circuits inside the kernel; at tile_words 8 with a small
    group the block is narrower than one 128-lane vreg row.  The dense
    pack has the layout ``TileStore.device_packs`` gives it, and gathering
    its tiles must not copy the whole pack (a 64-word-wide pack did)."""
    from repro.kernels import tiled_scan
    from repro.storage.tilestore import TileStore

    circuits = (
        build_threshold_circuit(8, 2, "ssum"),
        build_threshold_circuit(5, 3, "ssum"),
        build_threshold_circuit(3, 1, "ssum"),
    )
    m_max = max(c.n_inputs for c in circuits)
    b = tiled_scan.pick_tile_block(tile_words, m_max, k_max, group_tiles)
    fn = tiled_scan.block_runner(
        circuits, m_max, k_max, tile_words, use_pallas=True, interpret=False
    )
    nb, n_sel, dense, sparse, runs = 64, 1 << 16, 1 << 20, 1 << 12, 1 << 11
    width = TileStore.from_packed(
        np.zeros((1, tile_words), np.uint32), tile_words=tile_words,
        r=tile_words * 32,
    ).device_packs()[0].shape[1]
    pack_shape = (-(-(dense + 2) * tile_words // width), width)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        s((k_max, n_sel + 1), jnp.uint32), s((nb,), jnp.int32),
        s(pack_shape, jnp.uint32),
        s((nb * m_max * b + 1,), jnp.int32),
        s((sparse + 1,), jnp.uint16), s((sparse,), jnp.int32),
        s((sparse,), jnp.int32), s((257,), jnp.int32),
        s((runs + 1, 2), jnp.uint16), s((runs,), jnp.int32),
        s((runs,), jnp.int32), s((129,), jnp.int32),
        s((nb * k_max * b,), jnp.int32), s((nb * k_max * b,), jnp.int32),
    )
    assert b * tile_words == (1024 if tile_words == 64 else 32)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pack_bytes = pack_shape[0] * pack_shape[1] * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pack_bytes


def test_sharded_circuit_compiles_on_four_chips(topo):
    """The sharded index's shard_map runner over a four-chip mesh: one
    block of 2**27 rows per chip, no collective needed."""
    from jax.sharding import Mesh

    from repro.dist.query import _spmd_runner

    mesh = Mesh(topo.devices[:4], ("data",))
    names = tuple(f"s{i}" for i in range(N_STORES))
    circuit = build_query_circuit((Interval(2, 10),), N_STORES, names)
    fn = _spmd_runner(circuit, mesh, "data", N_STORES)
    cols = jax.ShapeDtypeStruct(
        (N_STORES, 4 * N_WORDS), jnp.uint32,
        sharding=NamedSharding(mesh, P(None, "data")),
    )
    compiled = fn.lower(cols).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == N_STORES * N_WORDS * 4
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
