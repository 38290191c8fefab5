"""The ad-hoc loop's query stream (CPU, small sizes): every seed does the
same work, set-up covers every shape the window sends, and no window
query repeats one asked before."""
import numpy as np
import pytest

from bench_testkit import small_tree
from yardstick import harness, words


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    bench_dir = small_tree(tmp_path_factory.mktemp("loop"))
    bench = harness.load_json(bench_dir.parent / "BENCHMARK.json")
    spec = harness.resolve(bench, "uniform_adhoc", bench_dir)
    spec["traffic"]["stream_length"] = 512

    def make(seed):
        c = harness.Cell(spec, seed, None)
        c.make_data(bench_dir)
        return c

    loop = harness.load_module("loops", spec["traffic"]["loop"], bench_dir)
    return make, loop


def test_every_seed_sends_the_same_sizes_in_the_same_order(cell):
    make, loop = cell
    a, b = (loop.queries(make(s)) for s in (2**33 + 5, 17))
    assert [(len(q[1]), q[2]) for q in a] == [(len(q[1]), q[2]) for q in b]
    assert [q[1] for q in a] != [q[1] for q in b]


def test_members_are_the_columns_holding_a_row(cell):
    make, loop = cell
    c = make(3)
    bits = words.unpack_host(np.asarray(c.cols))
    for _op, members, _t in loop.queries(c)[:64]:
        held = np.flatnonzero(bits[list(members)].all(axis=0)
                              & ~np.delete(bits, list(members), axis=0).any(axis=0))
        assert held.size >= 1


def test_warm_up_covers_every_shape_and_repeats_no_window_query(cell):
    make, loop = cell
    c = make(11)
    warm, stream = loop._pairs(c)
    drawn = loop._draw(c, warm + stream)
    shapes = {(len(q[1]), q[2]) for q in drawn[:len(warm)]}
    assert {(len(q[1]), q[2]) for q in drawn[len(warm):]} == shapes
    assert len(set(drawn)) == len(drawn)


def test_a_stream_that_runs_out_is_an_error(cell):
    make, loop = cell

    class Client:
        requests, attempted, answered = [], 0, 0

        def run_batch(self, descs):
            return [None], 0.0

    class Sample:
        def offer(self, item):
            pass

    with pytest.raises(RuntimeError, match="ran out"):
        loop.run({"client": Client(), "stream": [("threshold", (0,), 1)] * 3},
                 60.0, Sample())
