"""Row-sharded execution engine (repro.dist.query).

Oracle parity: every backend on a ShardedTileStore must be bit-identical
to the unsharded TileStore result -- including a shard with ZERO dirty
tiles and a partial final tile in the last shard.  The mesh-dependent
path (shard_map) runs in-process when 8 XLA devices exist (the CI
tier1-sharded job forces them) and always via a subprocess with
XLA_FLAGS set.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitmaps import unpack
from repro.core.threshold import ALGORITHMS
from repro.query import And, BitmapIndex, Col, Interval, Not, Threshold

TILE_BITS = 64 * 32
N_SHARDS = 8
TILES_PER_SHARD = 2

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(script: str):
    res = subprocess.run(
        [sys.executable, "-c", script], env=ENV, capture_output=True, text=True,
        timeout=600,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    return res.stdout


def _mixed_bits(n=10, seed=0, tail_bits=700):
    """Row space of 8 shards (~2 tiles each) + a partial final tile.

    Shard 0 is ALL-ZERO (zero dirty tiles); shards 1-3 are clean-heavy
    (cf >= 0.9); shards 4-7 are dense (cf = 0.0); the tail lands in the
    last shard's final, partial tile.  Tiles are mapped to shards with the
    engine's own boundary function so the layout matches exactly.
    """
    from repro.dist.query import shard_boundaries

    rng = np.random.default_rng(seed)
    n_tiles = N_SHARDS * TILES_PER_SHARD
    r = n_tiles * TILE_BITS + tail_bits
    total_tiles = n_tiles + 1  # the tail occupies one extra, partial tile
    bounds = shard_boundaries(total_tiles, N_SHARDS)
    shard_of = {}
    for s, (t0, t1) in enumerate(bounds):
        for tj in range(t0, t1):
            shard_of[tj] = s
    bits = np.zeros((n, r), bool)
    for i in range(n):
        for tj in range(total_tiles):
            lo, hi = tj * TILE_BITS, min((tj + 1) * TILE_BITS, r)
            shard = shard_of[tj]
            if shard == 0:
                continue  # zero-dirty shard
            if shard < 4:  # clean-heavy: mostly all-zero / all-one tiles
                u = rng.random()
                if u < 0.5:
                    pass
                elif u < 0.95:
                    bits[i, lo:hi] = True
                else:
                    bits[i, lo:hi] = rng.random(hi - lo) < 0.35
            else:  # dense
                bits[i, lo:hi] = rng.random(hi - lo) < 0.35
    return bits


@pytest.fixture(scope="module")
def mixed_index():
    bits = _mixed_bits()
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    return bits, idx, idx.shard(n_shards=N_SHARDS)


def test_shard_layout(mixed_index):
    bits, idx, sidx = mixed_index
    assert sidx.n_shards == N_SHARDS
    # partial final tile lives in the last shard
    last = sidx.store.shards[-1]
    assert last.n_words < last.n_tiles * last.tile_words
    assert last.r < last.n_words * 32
    # shard 0 has zero dirty tiles
    assert sidx.store.shards[0].dirty_words == 0
    # word offsets tile the global row space exactly
    offs = list(sidx.store.word_offsets) + [idx.n_words]
    assert offs[0] == 0 and all(a < b for a, b in zip(offs, offs[1:]))


def test_every_backend_sharded_matches_unsharded_oracle(mixed_index):
    """Satellite: each ALGORITHMS backend, forced on every shard, must be
    bit-identical to the same backend on the unsharded TileStore index."""
    bits, idx, sidx = mixed_index
    n, r = bits.shape[0], bits.shape[1]
    counts = bits.sum(0)
    for alg in ALGORITHMS:
        t = {"wide_or": 1, "wide_and": n, "sopckt": 2}.get(alg, 4)
        q = Threshold(t)
        want = np.asarray(idx.execute(q, backend=alg))
        got = np.asarray(sidx.execute(q, backend=alg).gather())
        np.testing.assert_array_equal(got, want, err_msg=f"sharded {alg}")
        np.testing.assert_array_equal(
            np.asarray(unpack(jnp.asarray(got), r)), counts >= t,
            err_msg=f"{alg} vs scancount oracle",
        )


def test_mixed_density_heterogeneous_plan(mixed_index):
    """Acceptance: half-clean/half-dense shards produce >= 2 distinct
    per-shard backends and execute bit-identically to the unsharded oracle."""
    bits, idx, sidx = mixed_index
    q = Threshold(4)
    plan = sidx.plan(q)
    assert len(plan.distinct) >= 2, plan.backends
    assert "tiled_fused" in plan.distinct, plan.backends
    got = np.asarray(sidx.execute(q).gather())
    np.testing.assert_array_equal(got, np.asarray(idx.execute(q, backend="ssum")))
    info = sidx.last_info
    assert info["mode"] == "per_shard"
    assert info["backends"] == plan.backends
    # the clean shards actually skipped: far fewer words gathered than dense
    assert info["dirty_words_gathered"] < bits.shape[0] * idx.n_words


def test_composite_query_sharded(mixed_index):
    bits, idx, sidx = mixed_index
    q = And(Interval(2, 6), Not(Threshold(9)))
    got = np.asarray(sidx.execute(q).gather())
    np.testing.assert_array_equal(got, np.asarray(idx.execute(q, backend="circuit")))


def test_execute_many_sharded(mixed_index):
    bits, idx, sidx = mixed_index
    qs = [Threshold(2), Threshold(8), Interval(1, 3)]
    got = sidx.execute_many(qs)
    for q, res in zip(qs, got):
        np.testing.assert_array_equal(
            np.asarray(res.gather()),
            np.asarray(idx.execute(q, backend="circuit")),
            err_msg=str(q),
        )


def test_add_column_shard_wise_no_gather(mixed_index):
    """Results feed back as sharded columns; stale references keep working."""
    bits, idx, sidx = mixed_index
    res = sidx.execute(Threshold(4))
    sidx2 = sidx.add_column("hot", res)
    assert "hot" in sidx2 and "hot" not in sidx
    assert sidx2.n == sidx.n + 1
    q = And(Col("hot"), Threshold(2))
    idx2 = idx.add_column("hot", idx.execute(Threshold(4), backend="ssum"))
    np.testing.assert_array_equal(
        np.asarray(sidx2.execute(q).gather()),
        np.asarray(idx2.execute(q, backend="circuit")),
    )
    # the old sharded index still executes against its own schema
    np.testing.assert_array_equal(
        np.asarray(sidx.execute(Threshold(4)).gather()),
        np.asarray(idx.execute(Threshold(4), backend="ssum")),
    )


def test_replace_column_immutable(mixed_index):
    bits, idx, sidx = mixed_index
    flipped = ~np.asarray(unpack(jnp.asarray(idx.column("c0")), bits.shape[1]))
    from repro.core.bitmaps import pack

    new = pack(jnp.asarray(flipped[None]))[0]
    sidx2 = sidx.replace_column("c0", sidx.store.split(new))
    got0 = np.asarray(sidx.column("c0"))
    got1 = np.asarray(sidx2.column("c0"))
    assert not np.array_equal(got0, got1)
    np.testing.assert_array_equal(got0, np.asarray(idx.column("c0")))


def test_from_sharded_round_trip(mixed_index):
    bits, idx, sidx = mixed_index
    back = BitmapIndex.from_sharded(sidx)
    assert back.names == idx.names and back.r == idx.r
    np.testing.assert_array_equal(np.asarray(back.columns), np.asarray(idx.columns))


def test_single_shard_degenerates_to_unsharded(mixed_index):
    bits, idx, sidx = mixed_index
    s1 = idx.shard(n_shards=1)
    assert s1.n_shards == 1
    got = np.asarray(s1.execute(Threshold(4)).gather())
    np.testing.assert_array_equal(got, np.asarray(idx.execute(Threshold(4), backend="ssum")))


# -- mesh paths (8 XLA devices: in-process under the CI sharded job) --------

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 XLA devices (tier1-sharded job)"
)


@needs_mesh
def test_shard_map_path_in_process():
    from repro.dist.mesh import make_host_mesh

    bits = np.random.default_rng(5).random((10, 16 * TILE_BITS + 300)) < 0.3
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    mesh = make_host_mesh(data=8)
    sidx = idx.shard(mesh=mesh)
    q = And(Interval(2, 6), Not(Threshold(9)))
    res = sidx.execute(q)
    assert sidx.last_info["mode"] == "shard_map"
    np.testing.assert_array_equal(
        np.asarray(res.gather()), np.asarray(idx.execute(q, backend="circuit"))
    )


def test_shard_map_acceptance_subprocess():
    """Always-on acceptance check on a real 8-device host platform: one
    compiled circuit under shard_map, heterogeneous per-shard plans on
    mixed-density data, bit-identical to the unsharded oracle."""
    _run(
        """
import numpy as np, jax, jax.numpy as jnp
assert len(jax.devices()) == 8
from repro.dist.mesh import make_host_mesh
from repro.query import BitmapIndex, Threshold
TILE_BITS = 64 * 32
rng = np.random.default_rng(0)
n, n_tiles = 10, 16
r = n_tiles * TILE_BITS + 700
bits = np.zeros((n, r), bool)
for i in range(n):
    for tj in range(n_tiles + 1):
        lo, hi = tj * TILE_BITS, min((tj + 1) * TILE_BITS, r)
        if tj < n_tiles // 2:
            bits[i, lo:hi] = rng.random(hi - lo) < 0.35
        else:
            u = rng.random()
            if u < 0.475:
                pass
            elif u < 0.95:
                bits[i, lo:hi] = True
            else:
                bits[i, lo:hi] = rng.random(hi - lo) < 0.35
idx = BitmapIndex.from_dense(jnp.asarray(bits))
mesh = make_host_mesh(data=8)
sidx = idx.shard(mesh=mesh)
assert sidx.n_shards == 8

# heterogeneous plan on mixed density data
plan = sidx.plan(Threshold(5))
assert len(plan.distinct) >= 2, plan.backends
got = np.asarray(sidx.execute(Threshold(5)).gather())
want = np.asarray(idx.execute(Threshold(5), backend="ssum"))
assert np.array_equal(got, want)

# dense-everywhere query runs as ONE shard_map
dense_idx = BitmapIndex.from_dense(jnp.asarray(
    np.random.default_rng(1).random((8, 8 * TILE_BITS)) < 0.4))
sdense = dense_idx.shard(mesh=mesh)
res = sdense.execute(Threshold(4))
assert sdense.last_info["mode"] == "shard_map", sdense.last_info
assert np.array_equal(np.asarray(res.gather()),
                      np.asarray(dense_idx.execute(Threshold(4), backend="ssum")))
print("sharded acceptance OK")
"""
    )


def test_fused_bare_threshold_reads_each_shards_planes(mixed_index):
    """The fused kernel reads each shard's members in place from that
    shard's plane view -- same words as the rows path (``ssum`` gathers
    member rows), nothing gathered."""
    bits, idx, sidx = mixed_index
    names = idx.names
    q = Threshold(3, over=tuple(names[i] for i in (7, 2, 5, 0, 9)))
    want = np.asarray(idx.execute(q, backend="ssum"))
    assert idx.last_info["members"] == "rows"
    got = np.asarray(sidx.execute(q, backend="fused").gather())
    np.testing.assert_array_equal(got, want)
    info = sidx.last_info
    assert info["members"] == "planes" and info["dirty_words_gathered"] == 0
    assert [i["members"] for i in info["per_shard"]] == ["planes"] * N_SHARDS
