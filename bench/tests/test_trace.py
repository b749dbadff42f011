"""The trace reduction's arithmetic, on hand-made intervals and on a
trace recorded on the chip."""
import glob
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == \
        [(0, 4), (5, 10)]


def test_minus_keeps_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert tr.minus(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]


def test_leaf_ops_drop_enclosing_events():
    ev = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 30, 40),
          ("copy.3", 120, 130)]
    assert tr.leaf_ops(ev) == [("fusion.1", 10, 20), ("fusion.2", 30, 40),
                               ("copy.3", 120, 130)]


def synthetic():
    """Two devices over a stretch [0, 1000) ns. Device 0: compute
    overlapping an all-reduce in part; device 1: a collective-permute
    with no compute beside it. Host spans: data then step_fn."""
    return tr.Trace(
        devices={
            "/device:TPU:0": [("fusion.1", 0, 300), ("fusion.2", 250, 400),
                              ("all-reduce.1", 350, 500),
                              ("fusion.3", 800, 900)],
            "/device:TPU:1": [("fusion.1", 100, 200),
                              ("collective-permute-start.2", 200, 260),
                              ("collective-permute-done.2", 600, 700)],
        },
        spans=[("bench.traced", 0, 1000), ("bench.data", 0, 550),
               ("bench.step_fn", 550, 1000), ("bench.other", 560, 570)])


def test_summarize_busy_collective_exposed_and_gaps():
    s = tr.summarize(synthetic())
    d0 = s["per_device"]["/device:TPU:0"]
    # busy: [0, 500) ∪ [800, 900)
    assert d0["busy_s"] == pytest.approx(600e-9)
    assert d0["collective_s"] == pytest.approx(150e-9)
    # the all-reduce is hidden behind fusion.2 until 400
    assert d0["exposed_collective_s"] == pytest.approx(100e-9)
    assert d0["other_s"] == pytest.approx(500e-9)
    assert d0["idle_s"] == pytest.approx(400e-9)
    d1 = s["per_device"]["/device:TPU:1"]
    assert d1["collective_s"] == pytest.approx(160e-9)
    assert d1["exposed_collective_s"] == pytest.approx(160e-9)
    assert d1["idle_s"] == pytest.approx(1000e-9 - 260e-9)
    assert s["mean"]["idle_s"] == pytest.approx((400e-9 + 740e-9) / 2)
    assert s["window_s"] == pytest.approx(1000e-9)
    # device 0's gaps: [500, 800) under step_fn (its middle, 650, is past
    # the short span "other"), [900, 1000) under step_fn
    assert s["idle_gaps"] == [["step_fn", pytest.approx(300e-9)],
                              ["step_fn", pytest.approx(100e-9)]]
    names = [n for n, _ in s["device_ops"]]
    assert names[0] == "fusion.1"


def test_gap_label_is_innermost_span():
    spans = [("bench.traced", 0, 100), ("bench.step_fn", 0, 100),
             ("bench.data", 40, 60)]
    assert tr.label(spans, 45, 55) == "data"
    assert tr.label(spans, 0, 10) == "step_fn"
    assert tr.label([("bench.traced", 0, 100)], 0, 10) == "outside_spans"


def test_collective_names():
    for op in ("all-reduce.3", "all-reduce-start.1", "all-reduce-done",
               "collective-permute-start.2", "all-gather.1",
               "reduce-scatter.4"):
        assert tr.is_collective(op)
    for op in ("fusion.12", "copy-start.1", "convolution.2", "while.1"):
        assert not tr.is_collective(op)


def test_recorded_trace_roundtrip():
    """Stretches recorded on TPU v5e chips (the benchmark's own spans
    around jobs of a small program): the reduction keeps its invariants
    on real events."""
    paths = sorted(glob.glob(os.path.join(DATA, "*.trace.json")))
    assert paths, "no recorded trace beside the test"
    for p in paths:
        with open(p) as f:
            t = tr.Trace.from_json(f.read())
        s = tr.summarize(t)
        for d in s["per_device"].values():
            assert 0 < d["busy_s"] <= s["window_s"] + 1e-12
            assert d["busy_s"] == pytest.approx(
                d["other_s"] + d["exposed_collective_s"])
            assert d["idle_s"] == pytest.approx(s["window_s"] - d["busy_s"])
            assert 0 <= d["exposed_collective_s"] <= d["collective_s"]
        for ops in t.devices.values():
            # leaves only: no loop around its body is left
            assert not any(n.lstrip("%").startswith("while") for n, _, _ in ops)
            assert tr.leaf_ops(ops) == sorted(ops, key=lambda e: (e[1], -e[2]))
        # the device's work lies inside the host's job spans once aligned
        jobs = tr.union([(a, b) for n, a, b in t.spans if n == "bench.job"])
        for ops in t.devices.values():
            busy = tr.union([(a, b) for _, a, b in ops])
            assert tr.length(tr.minus(busy, jobs)) <= 0.01 * tr.length(busy)
        assert tr.Trace.from_json(t.to_json()) == t
