"""Paper dataset generators + planner rules."""
import numpy as np

from repro.core.bitmaps import cardinality
from repro.core.planner import plan_threshold
from repro.data.paper_datasets import (
    clustered_set,
    similarity_query,
    synthetic_dataset,
    uniform_set,
)


def test_synthetic_dataset_paper_5_3():
    packed, r, lists = synthetic_dataset("uniform", "dense", n_bitmaps=8, card=500, seed=1111)
    assert r == 1500
    assert all(len(l) == 500 for l in lists)
    assert np.asarray(cardinality(packed)).tolist() == [500] * 8
    packed_c, r_c, lists_c = synthetic_dataset("clustered", "dense", n_bitmaps=4, card=500)
    # clustered data has far fewer runs than uniform at equal cardinality
    from repro.core.blockrle import runcount

    assert runcount(packed_c) < runcount(packed[:4])


def test_similarity_query_selects_containing_bitmaps():
    rng = np.random.default_rng(0)
    lists = [np.sort(rng.choice(1000, 100, replace=False)) for _ in range(20)]
    sel, rid = similarity_query(lists, n=5, rid=int(lists[3][0]))
    for i in set(sel):
        l = lists[i]
        j = np.searchsorted(l, rid)
        # either contains rid, or was a replicated filler when < n contain it
    assert len(sel) == 5


def test_planner_rules():
    assert plan_threshold(8, 1).algorithm == "wide_or"
    assert plan_threshold(8, 8).algorithm == "wide_and"
    assert plan_threshold(64, 2).algorithm == "looped"
    assert plan_threshold(64, 30, clean_fraction=0.9).algorithm == "rbmrg_block"
    assert plan_threshold(64, 30).algorithm == "fused"
    assert plan_threshold(64, 62, density=1e-4, on_device=False).algorithm == "dsk"
