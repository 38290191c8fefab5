"""The comparison's control reads not correct, at a size a test run
holds (CPU).  On the chip ``bench/control.py`` runs it at each cell's own
size."""
import pytest

from bench_testkit import BENCH, small_tree

import control


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return small_tree(tmp_path_factory.mktemp("control"))


def test_control_is_not_correct(tree):
    lines = control.main(["--workload", "uniform_adhoc", "--seeds", "1,2,3"],
                         bench_dir=tree, require_tpu=False)
    assert len(lines) == 3
    for line in lines:
        assert line["correct"] is False
        assert line["check"]["wrong_words"]["value"] > 0
        assert line["check"]["answers_checked"]["value"] >= 1
    assert (BENCH / "control.py").is_file()
