"""The harness's own arithmetic: the trace reduction on a synthetic
trace, percentiles, and the open-loop schedule."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import corpus  # noqa: E402
import trace_reduce as tr  # noqa: E402
from stats import percentile  # noqa: E402

TRACE = {
    "devices": {
        "/device:TPU:0": {
            # two overlapping ops, a gap of 2 s, one more op
            "ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0.0, 1.0),
                    ("%copy.2 = f32[8]{0} copy(f32[8]{0} %q)", 0.5, 1.5),
                    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 3.5, 4.0)],
            "modules": [("jit_search(123)", 0.0, 1.5), ("jit__lambda(9)", 3.5, 4.0)],
        }
    },
    "spans": [("bench.encoder.encode", 1.6, 3.4), ("bench.index.search", 0.0, 1.55)],
}


def test_busy_is_the_union_of_intervals():
    assert tr.busy_seconds(TRACE["devices"]["/device:TPU:0"]["ops"]) == pytest.approx(2.0)


def test_reduce_busy_modules_ops_and_gaps():
    out = tr.reduce(TRACE)
    assert out["busy_s"] == pytest.approx(2.0)
    assert 1.0 - out["busy_s"] / (out["last_s"] - out["first_s"]) == pytest.approx(0.5)
    assert out["per_module_s"] == {"jit_search": pytest.approx(1.5), "jit__lambda": pytest.approx(0.5)}
    assert out["module_runs"]["jit_search"][0] == 1
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    # the one idle gap (1.5 .. 3.5) lies under the encoder's span
    assert out["idle_gaps"] == [["bench.encoder.encode", pytest.approx(2.0)]]


def test_gap_outside_every_span_says_so():
    assert tr.attribute((10.0, 11.0), TRACE["spans"]) == "outside-harness-spans"


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"devices": {}, "spans": []})["busy_s"] == 0.0


@pytest.mark.parametrize("p,want", [(0, 1.0), (50, 2.5), (95, 3.85), (100, 4.0)])
def test_percentile_is_numpys(p, want):
    values = [4.0, 1.0, 3.0, 2.0]
    assert percentile(values, p) == pytest.approx(want)
    assert percentile(values, p) == pytest.approx(float(np.percentile(values, p)))


def test_arrivals_fill_the_window_and_are_the_mixes_own():
    a = corpus.arrivals(200, 30.0, 7)
    assert len(a) == 200 and 0 < a[0] and a[-1] < 30.0 and np.all(np.diff(a) > 0)
    # exponential gaps: as many short ones as long ones make bursts
    gaps = np.diff(a, prepend=0)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.25)
    assert np.allclose(a, corpus.arrivals(200, 30.0, 7))
    assert not np.allclose(a, corpus.arrivals(200, 30.0, 8))


def test_lateness_and_latency_run_from_the_due_time():
    # a question due at 1.0, sent at 1.2, answered at 1.5: 200 ms late, 500 ms latency
    due, sent, done = 1.0, 1.2, 1.5
    assert (sent - due) * 1e3 == pytest.approx(200.0)
    assert (done - due) * 1e3 == pytest.approx(500.0)


def test_doc_lengths_are_one_multiset_for_every_seed():
    spec = {"scale": 14, "alpha": 1.2, "cap": 510, "shuffle_within": 512}
    a = corpus.doc_lengths(2048, spec, 3, seed=1)
    b = corpus.doc_lengths(2048, spec, 3, seed=99)
    assert sorted(a) == sorted(b) and a.max() == 510 and a.min() >= 14
    for at in range(0, 2048, 512):  # commit by commit, too
        assert sorted(a[at:at + 512]) == sorted(b[at:at + 512])


def test_a_stall_at_the_windows_end_lowers_the_ingest_rate():
    import loader

    window_rate = loader.module("generators", "bulk_ingest").window_rate
    steady = [(1.5 * i, 512 * i) for i in range(8, 60)]     # a commit every 1.5 s
    t0 = steady[2][0]
    assert window_rate(steady[:20], t0, 51.0) is None        # not closed yet
    whole = window_rate(steady, t0, 51.0)
    assert whole["seconds"] == pytest.approx(51.0) and whole["docs"] == 34 * 512
    assert whole["start"] == steady[2][1] and whole["commits"] == 34
    # the same commits, the last ten seconds of the 51 stalled: the close
    # waits for the next completion, and every stalled second counts
    stalled = [(t, c) if t <= t0 + 41 else (t + 10.0, c) for t, c in steady]
    late = window_rate(stalled, t0, 51.0)
    assert late["seconds"] == pytest.approx(52.0) and late["docs"] == 28 * 512
    assert late["docs"] / late["seconds"] < 0.81 * whole["docs"] / whole["seconds"]
    assert late["docs_at_seconds"] == 27 * 512
