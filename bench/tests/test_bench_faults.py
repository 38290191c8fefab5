"""``correct`` comes out false when the timed path is broken underneath,
for each fault the cell can have; and true when it is not (CPU).  The
client sends one query at a time, so no batch has a half to leave out.

Each fault wraps the index's ``execute_many``, the call through which
the server answers every batch."""
import pytest

from bench_testkit import run_cell, small_tree


def _wrap(cell, change):
    real = cell.idx.execute_many

    def broken(queries, **kw):
        return change(list(real(queries, **kw)))

    cell.idx.execute_many = broken


def altered(cell):
    """One answer altered where it is produced: a bit flipped."""
    _wrap(cell, lambda outs: [outs[0].at[0].set(outs[0][0] ^ 1)] + outs[1:])


def stale(cell):
    """An answer crossed between requests: each query gets the answer of
    the query before it."""
    last = []

    def change(outs):
        prev = last[:] or outs
        last[:] = outs
        return prev
    _wrap(cell, change)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return small_tree(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("fault", [altered, stale], ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(tree, capsys, fault):
    rc, line = run_cell(tree, "uniform_adhoc", fault=fault, capsys=capsys)
    assert rc == 0 and line["correct"] is False
    assert line["check"]["wrong_words"]["value"] > 0


def test_a_sound_run_is_correct(tree, capsys):
    rc, line = run_cell(tree, "uniform_adhoc", capsys=capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["check"]["answers_checked"]["value"] >= 1
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "check"}
    assert list(line)[-1] == "check"
