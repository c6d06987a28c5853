"""The scoped-trace reader and its reductions: `scopes.load` against
`jax.profiler.ProfileData` on the recorded chip traces, the arithmetic on
hand-made operations, and the ``quantile_ms.train`` reader."""
import gzip
import os
from types import SimpleNamespace

import pytest

from harness import common
from metrics import kernels as K
from metrics import scopes as SC
from metrics import trace as TR

MS = 1e6        # ns
DATA = os.path.join(os.path.dirname(__file__), "data")
SAMPLE = os.path.join(DATA, "sample.xplane.pb.gz")
SCOPED_SAMPLE = os.path.join(DATA, "sample_scoped.xplane.pb.gz")

SCAN = "jit(boost_scan)/while/body/closed_call/"
GROW = SCAN + "jit(grow_tree)/"
LEVEL = GROW + "hist/jit(histogram_splits_level)/"


def op(a, b, name="%op.1", tf_op="", program="jit_boost_scan(1)"):
    return SC.ScopedOp(a * MS, b * MS, name, tf_op, program)


def unpack(path, tmp_path):
    with gzip.open(path, "rb") as f:
        (tmp_path / "t.xplane.pb").write_bytes(f.read())
    return str(tmp_path)


def test_in_scope_reads_components_before_the_operation():
    assert SC.in_scope(LEVEL + "gather/jit(_pad)/pad", ("hist", "gather"))
    assert SC.in_scope(LEVEL + "gather/add", ("hist",))
    # a gather primitive is not the gather scope
    assert not SC.in_scope(LEVEL + "epilogue/jit(take)/gather",
                           ("hist", "gather"))
    assert not SC.in_scope(GROW + "route/gather", ("hist", "gather"))
    # the kernels are timed apart
    assert not SC.in_scope(LEVEL + "jit(hist_tiles_pallas)/pallas_call",
                           ("hist",))
    assert not SC.in_scope("", ("grad",))
    assert SC.in_scope(SCAN + "grad/jit(grad_hess)/exp", ("grad",))


def test_round_split_adds_up_to_the_scan_program():
    # a scan loop holding a grad op, a gather, the tiles kernel, an
    # epilogue op, a route op, one op under no scope and the loop's own
    # time; then a binning program and another program
    ops = [op(0, 20, "%while.1"),
           op(0, 1, "%fusion.1", SCAN + "grad/mul"),
           op(1, 3, "%fusion.2", LEVEL + "gather/gather"),
           op(3, 9, "%hist_tiles_pallas.3",
              LEVEL + "jit(hist_tiles_pallas)/pallas_call"),
           op(9, 12, "%scatter.4", LEVEL + "epilogue/scatter-add"),
           op(12, 13, "%fusion.5", GROW + "route/gather"),
           op(13, 15, "%fusion.6", SCAN + "add"),
           op(22, 26, "%fusion.7", "jit(apply_quantizer)/lt",
              "jit_apply_quantizer(2)"),
           op(27, 28, "%copy.8", "", "jit_concatenate(3)")]
    split = SC.round_split(ops, 0, 30 * MS, rounds=2, fits=1)
    assert split["round_grad_ms"] == pytest.approx(0.5)
    assert split["hist_gather_ms"] == pytest.approx(1.0)
    assert split["hist_epilogue_ms"] == pytest.approx(1.5)
    assert split["hist_subtract_ms"] == 0
    assert split["round_route_ms"] == pytest.approx(0.5)
    # the unscoped op (2 ms) and the loop's own time (5 ms), per round
    assert split["round_unscoped_ms"] == pytest.approx(3.5)
    assert split["scan_kernels_ms"] == pytest.approx(3.0)
    assert split["scan_xla_ms"] == pytest.approx(7.0)
    assert split["bin_ms_per_fit"] == pytest.approx(4.0)
    assert split["other_programs_xla_ms"] == pytest.approx(0.5)
    # busy time = the scan's XLA and kernel time + binning + the rest
    busy = TR.busy_ns([(o.start_ns, o.end_ns, o.name) for o in ops],
                      0, 30 * MS) * 1e-6 / 2
    assert busy == pytest.approx(split["scan_xla_ms"]
                                 + split["scan_kernels_ms"]
                                 + split["bin_ms_per_fit"] / 2
                                 + split["other_programs_xla_ms"])


def test_spans_and_gap_owners():
    host = [(0, 30 * MS, "bench.window"), (1 * MS, 14 * MS, "bench.fit"),
            (1 * MS, 13 * MS, "sketchboost.fit"),
            (2 * MS, 5 * MS, "sketchboost.quantiles"),
            (5 * MS, 6 * MS, "sketchboost.bin"),
            (29 * MS, 31 * MS, "sketchboost.quantiles")]
    assert SC.span_ns(host, "quantiles", 0, 30 * MS) == 4 * MS
    assert SC.owner(host, 3 * MS) == "sketchboost.quantiles"
    assert SC.owner(host, 12 * MS) == "sketchboost.fit"
    assert SC.owner(host, 13.5 * MS) == "bench.fit"
    assert SC.owner(host, 40 * MS) == "none"
    ops = [(0, 2 * MS, "a"), (5 * MS, 5.5 * MS, "b"), (6 * MS, 30 * MS, "c")]
    gaps = SC.owned_gaps(ops, host, 0, 30 * MS)
    assert gaps == [("sketchboost.quantiles", pytest.approx(0.003)),
                    ("sketchboost.bin", pytest.approx(0.0005))]


def test_quantile_reader():
    read = common.load_reader("quantile_ms.train")
    red = TR.Reduction(window_s=0.03, busy_s=0.02, ops=[], lo=0, hi=30 * MS,
                       host=[(2 * MS, 5 * MS, "sketchboost.quantiles"),
                             (10 * MS, 12 * MS, "sketchboost.quantiles")])
    assert read(SimpleNamespace(red=red, fits=2)) == pytest.approx(2.5)
    # a program without the span (the parent of this reader) reads nothing
    assert read(SimpleNamespace(red=red._replace(host=[]), fits=2)) is None
    assert read(SimpleNamespace(red=None, fits=2)) is None


def test_load_matches_profile_data_on_the_recorded_trace(tmp_path):
    d = unpack(SAMPLE, tmp_path)
    ours, theirs = SC.load(d), TR.load(d)
    assert sorted(ours) == sorted(theirs.device) == [0]
    assert [(o.start_ns, o.end_ns, o.name) for o in ours[0]] \
        == theirs.device[0]
    by_name = {o.name: o for o in ours[0]}
    assert by_name["%hist_tiles_pallas.72"].tf_op == (
        "jit(boost_scan)/while/body/closed_call/jit(grow_tree)/"
        "jit(histogram_splits_level)/jit(hist_tiles_pallas)/pallas_call")
    assert by_name["%hist_tiles_pallas.72"].program.startswith(
        SC.SCAN_PROGRAM + "(")
    # only loop containers and a few copies carry no scope path
    assert all(o.program for o in ours[0])
    assert sum(1 for o in ours[0] if not o.tf_op) < len(ours[0]) / 10


def test_recorded_scoped_trace_adds_up(tmp_path):
    """Two 4-tree fits of ``otto.train`` cut to 16,384 rows, traced on a
    v5e chip by ``bench/tools/trace_sample.py`` from a program with the
    scopes, spans and kernel names of `repro.runtime.tracing`."""
    d = unpack(SCOPED_SAMPLE, tmp_path)
    ops, trace = SC.load(d)[0], TR.load(d)
    assert [(o.start_ns, o.end_ns, o.name) for o in ops] == trace.device[0]
    red = TR.reduce(trace)
    # the kernels keep their instruction names under ``name=``
    for names in (K.HIST, K.SPLIT, K.TRAVERSE):
        assert red.kernel_s(names) > 0
    rounds, fits = 8, 2
    split = SC.round_split(ops, red.lo, red.hi, rounds, fits)
    assert all(split[k] > 0 for k in SC.ROUND_SCOPES)
    # scopes + unscoped rest + kernels = the scan program's busy time
    scan = [(o.start_ns, o.end_ns, o.name) for o in ops
            if o.program.startswith(SC.SCAN_PROGRAM)]
    scan_ms = TR.busy_ns(scan, red.lo, red.hi) * 1e-6 / rounds
    parts = (sum(split[k] for k in SC.ROUND_SCOPES)
             + split["round_unscoped_ms"] + split["scan_kernels_ms"])
    assert parts == pytest.approx(scan_ms, rel=0.01)
    assert 0 <= split["round_unscoped_ms"] <= 0.05 * split["scan_xla_ms"]
    # ... and with binning and the other programs, the benchmark's
    # ``round_xla_ms`` (busy time outside the kernels)
    xla = red.other_s(K.PALLAS) * 1e3 / rounds
    assert (split["scan_xla_ms"] + split["bin_ms_per_fit"] * fits / rounds
            + split["other_programs_xla_ms"]) == pytest.approx(xla, rel=0.01)
    # the long idle gaps are the host quantile fits
    gaps = SC.owned_gaps(red.ops, trace.host, red.lo, red.hi, k=2)
    assert [g for g, _ in gaps] == ["sketchboost.quantiles"] * 2
    assert SC.span_ns(trace.host, "quantiles", red.lo, red.hi) > 0
