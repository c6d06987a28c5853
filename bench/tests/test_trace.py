"""The trace reduction: union, gaps, kernel sums and labels on hand-made
intervals, and `load` + `reduce` on a trace recorded on a v5e chip."""
import gzip
import os

import pytest

from metrics import trace as TR

MS = 1e6        # ns


def iv(a, b, name="op"):
    return (a * MS, b * MS, name)


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = [iv(0, 2), iv(1, 3), iv(5, 6), iv(9, 12)]
    assert TR.union(ops, 1 * MS, 10 * MS) == [(1 * MS, 3 * MS),
                                             (5 * MS, 6 * MS),
                                             (9 * MS, 10 * MS)]
    assert TR.busy_ns(ops, 1 * MS, 10 * MS) == 4 * MS


def test_gaps_are_the_complement_of_busy():
    ops = [iv(1, 3), iv(2, 4), iv(6, 7)]
    g = TR.gaps(ops, 0, 10 * MS)
    assert g == [(0, 1 * MS), (4 * MS, 6 * MS), (7 * MS, 10 * MS)]
    busy = TR.busy_ns(ops, 0, 10 * MS)
    assert busy + sum(e - s for s, e in g) == 10 * MS


def test_kernel_time_sums_matching_events_and_the_rest_is_other():
    # A loop holds its body: kernel time is not counted twice, and the
    # loop's own time is what its body leaves.
    ops = [iv(0, 8, "%while.2"), iv(0, 1, "%fusion.1"),
           iv(1, 4, "%hist_tiles_pallas.3"), iv(4, 5, "%split_scan_pallas.7"),
           iv(5, 7, "%copy.9")]
    assert TR.kernel_ns(ops, ("hist_tiles_pallas",), 0, 10 * MS) == 3 * MS
    assert TR.kernel_ns(ops, ("split_scan_pallas",), 0, 10 * MS) == 1 * MS
    assert TR.other_ns(ops, ("hist_tiles_pallas", "split_scan_pallas"),
                       0, 10 * MS) == 4 * MS
    own = {n: (e - s) / MS for s, e, n in TR.self_ns(ops)}
    assert own == {"%while.2": 1, "%fusion.1": 1, "%hist_tiles_pallas.3": 3,
                   "%split_scan_pallas.7": 1, "%copy.9": 2}


def test_op_name_drops_the_instruction_text():
    assert TR.op_name("%hist_tiles_pallas.72 = f32[60,66,256,128]{3,2,1,0} "
                      "custom-call(s32[60,1,16896] %reshape.6350)") \
        == "%hist_tiles_pallas.72"
    assert TR.op_name("copy.4") == "copy.4"


def test_gaps_are_labelled_by_what_the_host_was_doing():
    host = [iv(0, 10, "bench.window"), iv(2, 9, "bench.fit"),
            iv(3, 5, "PjitFunction(boost_scan)"), iv(6, 8, "np_quantiles")]
    ops = [iv(0, 3), iv(5, 6), iv(8, 10)]
    gaps = TR.top_gaps(ops, host, 0, 10 * MS)
    assert [round(s, 6) for _, s in gaps] == [0.002, 0.002]
    assert gaps[0][0] == "bench.fit / PjitFunction(boost_scan)"
    assert gaps[1][0] == "bench.fit / np_quantiles"
    assert TR.label(host, 9.5 * MS) == "bench.window"


def test_reduce_reads_the_window_from_the_window_span():
    trace = TR.Trace(device={0: [iv(1, 2, "a"), iv(3, 5, "b"),
                                 iv(20, 30, "c")]},
                     host=[iv(0, 4, TR.WINDOW_SPAN), iv(2, 10,
                                                        TR.WINDOW_SPAN)])
    red = TR.reduce(trace)
    assert red.window_s == pytest.approx(0.010)
    assert red.busy_s == pytest.approx(0.003)
    assert red.breakdown()["device_ops"][0] == ["b", pytest.approx(0.002)]
    assert TR.reduce(TR.Trace(device={0: []}, host=[])) is None


SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "sample.xplane.pb.gz")


def test_recorded_chip_trace(tmp_path):
    """Two 2-tree fits at dionis widths (4,096 rows) traced on a v5e chip
    by ``bench/tools/trace_sample.py`` (gzipped)."""
    from metrics import kernels as K
    with gzip.open(SAMPLE, "rb") as f:
        (tmp_path / "t.xplane.pb").write_bytes(f.read())
    trace = TR.load(str(tmp_path))
    assert 0 in trace.device and trace.device[0]
    red = TR.reduce(trace)
    assert 0 < red.busy_s < red.window_s
    # every kernel of the round is found by name, and the rest is XLA
    for names in (K.HIST, K.SPLIT, K.TRAVERSE):
        assert red.kernel_s(names) > 0
    assert 0 < red.other_s(K.PALLAS) < red.busy_s
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(label.startswith("bench.") for label, _ in bd["idle_gaps"])
