"""The trace reduction on hand-made intervals and on a small trace
recorded on a TPU v5e (``testdata/trace_small.json``: the first
operations of a ``train.mamba2-780m.share2`` window, exported by
``devtrace.export_events``)."""
import os

import pytest

import devtrace
import harness

SMALL = os.path.join(harness.BENCH_DIR, "testdata", "trace_small.json")


def test_merge_gaps_overlap():
    busy = devtrace.merge([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert devtrace.total(busy) == 7
    assert devtrace.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert devtrace.overlap(busy, [(2, 6), (8, 20)]) == 1 + 1 + 1


def test_self_times_subtract_nested_ops():
    recs = [("cond", 0, 10), ("kernel", 2, 5), ("fusion", 6, 7),
            ("after", 10, 12)]
    assert devtrace.self_times(recs) == [6, 3, 1, 2]


def test_innermost_span_names_gaps():
    spans = [["bench.window", 0, 100], ["bench.step", 10, 50],
             ["bench.fetch", 20, 30]]
    assert devtrace.innermost_span(spans, 25) == "bench.fetch"
    assert devtrace.innermost_span(spans, 40) == "bench.step"
    assert devtrace.innermost_span(spans, 60) == "no harness span"


def test_collective_time_exposed_only_without_compute_beside_it():
    ev = {"ops": {"/device:TPU:0": [
        ["all-reduce.1", 0, 10], ["fusion.2", 5, 8],
        ["while.3", 0, 20], ["all-gather.4", 12, 16]]},
        "hlo": {}, "spans": [["bench.window", 0, 20]]}
    t = devtrace.summarize_events(ev)
    assert t.collective_s == pytest.approx(14e-9)
    # fusion.2 overlaps 3 ns of the all-reduce; the enclosing loop is
    # no compute of its own
    assert t.collective_exposed_s == pytest.approx(11e-9)
    assert t.busy_s == pytest.approx(20e-9)


@pytest.fixture(scope="module")
def small():
    return harness.load_json(SMALL)


def test_recorded_trace_busy_and_gaps_fill_the_window(small):
    t = devtrace.summarize_events(small)
    assert 0 < t.busy_s <= t.window_s
    chip = next(iter(small["ops"]))
    lo, hi = next((s, e) for n, s, e in small["spans"]
                  if n == devtrace.WINDOW_SPAN)
    recs = [(n, max(s, lo), min(e, hi)) for n, s, e in small["ops"][chip]
            if e > lo and s < hi]
    busy = devtrace.merge((s, e) for _, s, e in recs)
    idle = devtrace.gaps(busy, lo, hi)
    assert devtrace.total(busy) + devtrace.total(idle) == hi - lo
    assert len(t.breakdown["device_ops"]) <= 10
    assert len(t.breakdown["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") or name == "no harness span"
               for name, _ in t.breakdown["idle_gaps"])


def test_recorded_trace_kernel_time_by_stable_name(small):
    t = devtrace.summarize_events(small)
    k = t.kernel_s(r"^sketch_flat")
    assert k is not None and k > 0
    by_hand = sum(e - s for n, s, e in next(iter(small["ops"].values()))
                  if n.startswith("sketch_flat")) / 1e9
    # the sketch kernels are leaves: their self time is their time
    # (the window may cut the last one)
    assert k == pytest.approx(by_hand, rel=0.05)
    assert "tpu_custom_call" in t.hlo[next(
        n for n in t.op_s if n.startswith("sketch_flat"))]
    assert t.kernel_s(r"no such kernel") is None
