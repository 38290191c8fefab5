"""The per-layer metrics that read the program's span trees (CPU): the
planner, the member statistics, dispatch and the queue wait, on hand-made
span trees and in a traced run of the cell."""
import pytest

from bench_testkit import run_cell, small_tree
from repro.obs.trace import Span
from yardstick import harness


def _span(name, wall_s, *children, **attrs):
    sp = Span(name, attrs)
    sp.wall_s = wall_s
    sp.children = list(children)
    return sp


def _request(plan_s, stats_s, dispatch_s, compile_s=None, wait_s=None):
    """One served request's span tree, as the program writes it; without
    ``stats_s`` and ``wait_s`` as a program that writes no ``member_stats``
    span and no ``queue_wait_s`` does."""
    plan = _span("plan", plan_s, *([_span("member_stats", stats_s)] if stats_s else []))
    disp = _span("dispatch", dispatch_s,
                 *([_span("compile", compile_s)] if compile_s else []))
    em = _span("execute_many", plan_s + dispatch_s + 0.001, plan, disp)
    attrs = {"batch": 1, "rids": [0]}
    if wait_s is not None:
        attrs["queue_wait_s"] = wait_s
    return _span("serve_batch", em.wall_s + 0.0005, em, **attrs)


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


NEW = ("plan_ms_per_query", "member_stats_ms_per_query", "dispatch_ms_per_query",
       "queue_wait_ms_per_query")


def test_new_metrics_on_a_synthetic_record():
    rec = {"answered": 2, "window_s": 1.0, "trace": None,
           "spans": [_request(0.030, 0.026, 0.008, wait_s=0.002),
                     _request(0.020, 0.016, 0.010, compile_s=0.004, wait_s=0.003)]}
    assert _read("plan_ms_per_query", rec) == pytest.approx(4.0)  # (4 + 4) / 2
    assert _read("member_stats_ms_per_query", rec) == pytest.approx(21.0)
    assert _read("dispatch_ms_per_query", rec) == pytest.approx(7.0)  # (8 + 6) / 2
    assert _read("queue_wait_ms_per_query", rec) == pytest.approx(2.5)
    # the three host layers lie inside execute_many
    parts = sum(_read(n, rec) for n in NEW[:3])
    assert parts <= _read("execute_ms_per_query", rec)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_says_nothing_without_spans(name):
    assert _read(name, {"answered": 5, "window_s": 1.0, "spans": [],
                        "trace": None}) is None
    # nothing answered: no per-query figure either
    rec = {"answered": 0, "window_s": 1.0, "trace": None,
           "spans": [_request(0.030, 0.026, 0.008, wait_s=0.002)]}
    assert _read(name, rec) is None


@pytest.mark.parametrize("name,want", [
    ("plan_ms_per_query", 30.0),  # the whole plan: no member_stats inside
    ("member_stats_ms_per_query", None),
    ("dispatch_ms_per_query", 8.0),
    ("queue_wait_ms_per_query", None),
])
def test_new_metric_on_a_program_without_the_new_spans(name, want):
    """Read over an older program, whose span trees hold no
    ``member_stats`` span and no ``queue_wait_s``, a metric gives what
    the trees hold, or None; it never raises."""
    rec = {"answered": 1, "window_s": 1.0, "trace": None,
           "spans": [_request(0.030, None, 0.008)]}
    got = _read(name, rec)
    assert got is None if want is None else got == pytest.approx(want)


def test_traced_run_reads_the_host_layers_inside_execute(tmp_path, capsys):
    """A traced CPU run of the cell: the span metrics are read, and the
    planner, member statistics and dispatch lie inside execute_many."""
    bench_dir = small_tree(tmp_path)
    rc, line = run_cell(bench_dir, "uniform_adhoc", seed=2**33 + 1, seconds=1.0,
                        trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NEW + ("execute_ms_per_query",):
        assert got[name] > 0, name
    parts = sum(got[n] for n in NEW[:3])
    assert parts <= got["execute_ms_per_query"]
