"""End-to-end system test: the paper's workload through the public API."""
import jax.numpy as jnp
import numpy as np

from repro.core import plan_threshold, rbmrg_block_threshold, threshold, unpack
from repro.storage import TileStore
from repro.data.paper_datasets import similarity_query, synthetic_dataset


def test_similarity_query_end_to_end():
    """The paper's motivating workload: items meeting >= T of N criteria,
    answered three ways (oracle, circuit, planner) with identical results."""
    packed, r, lists = synthetic_dataset("clustered", "dense", n_bitmaps=32, card=800, seed=3)
    sel, rid = similarity_query(lists, n=16, rid=int(lists[0][0]), seed=1)
    bm = jnp.asarray(packed[sel])
    t = 6
    oracle = np.asarray(unpack(threshold(bm, t, "scancount"), r))
    circuit = np.asarray(unpack(threshold(bm, t, "fused"), r))
    np.testing.assert_array_equal(oracle, circuit)
    # the query item itself must qualify (it is in every selected bitmap)
    assert oracle[rid]
    # planner route with tile stats from the storage engine
    stats = TileStore.from_packed(bm).block_stats()
    plan = plan_threshold(16, t, clean_fraction=stats.clean_fraction)
    if plan.algorithm == "rbmrg_block":
        out, info = rbmrg_block_threshold(bm, t, stats=stats)
        np.testing.assert_array_equal(np.asarray(unpack(out, r)), oracle)
        assert info["work_fraction"] <= 1.0
    # result is a bitmap: compose with a further AND (bitmap-index property)
    mask = threshold(bm, 1, "ssum")
    composed = jnp.bitwise_and(threshold(bm, t, "ssum"), mask)
    assert np.asarray(unpack(composed, r)).sum() == oracle.sum()
