"""Work counts come from shapes and leaf covers alone; the peak table
refuses an unknown chip."""
import numpy as np
import pytest

from metrics import work as W


def covers(depth, n, seed=0):
    rng = np.random.default_rng(seed)
    c = rng.multinomial(n, np.full(2 ** depth, 2.0 ** -depth))
    return c.astype(np.float64)


def test_node_rows_sum_leaf_covers():
    c = covers(3, 1000)
    levels = W.node_rows(c)
    assert [len(x) for x in levels] == [1, 2, 4]
    for lvl in levels:
        assert lvl.sum() == 1000
    assert levels[2][1] == c[2] + c[3]


def test_rows_built_is_root_plus_smaller_children():
    # Depth 2: the root's 10 rows are histogrammed, then level 1 (7 + 3)
    # builds only its smaller node; the leaves get no histogram.
    rows, nodes = W.hist_rows_built([4, 3, 1, 2])
    assert rows == 10 + 3
    assert nodes == 1 + 1
    rows, nodes = W.hist_rows_built([4, 3, 1, 2, 5, 5, 0, 9])
    assert rows == 29 + min(10, 19) + min(7, 3) + min(10, 9)
    assert nodes == 1 + 1 + 2


def test_pass_through_child_costs_nothing():
    # A node that sends every row left has an empty right child: nothing
    # to build below it.
    rows, _ = W.hist_rows_built([5, 0, 0, 0])
    assert rows == 5


@pytest.mark.parametrize("n", [4096, 100_000])
def test_hist_work_depends_on_shapes_and_covers_only(n):
    c = covers(6, n, seed=1)
    w = W.hist_work(c, m=60, k=5, n_bins=256)
    rows, nodes = W.hist_rows_built(c)
    # Codes (1 byte each) and k + 1 float32 stats per built row, one
    # (m, bins, k + 1) float32 histogram per built node: no tile count,
    # no 128-lane channel padding, no one-hot.
    assert w.bytes == rows * (60 + 6 * 4) + nodes * 60 * 256 * 6 * 4
    assert w.flops == rows * 60 * 6
    # Padding the row count to the kernel's 256-row tiles changes nothing:
    # the counts never see the tile size.
    assert W.hist_work(c.tolist(), m=60, k=5, n_bins=256) == w


def test_round_work_counts_the_d_wide_passes_once_each():
    c = covers(2, 1000)
    w = W.round_work(c, n=1000, n_eval=200, m=10, d=50, k=5, depth=2,
                     n_bins=16, dense_targets=False)
    h = W.hist_work(c, 10, 5, 16)
    f_bytes = 3 * 1000 * 50 * 4 + 2 * 1000 * 4 + 1000 * 2
    e_bytes = 2 * 200 * 50 * 4 + 200 * 4 + 200 * 2
    assert w.bytes == f_bytes + e_bytes + h.bytes
    assert w.flops == 2 * 1000 * 50 * 5 + 4 * 1000 * 50 + 2 * 200 * 50 \
        + h.flops


def test_round_work_without_a_sketch_counts_no_projection():
    c = covers(2, 1000)
    kw = dict(n=1000, n_eval=200, m=10, d=9, k=9, depth=2, n_bins=16,
              dense_targets=False)
    full = W.round_work(c, sketched=False, **kw)
    sk = W.round_work(c, **kw)
    assert sk.flops - full.flops == 2 * 1000 * 9 * 9
    assert sk.bytes == full.bytes


def test_share_takes_the_binding_peak():
    kind = "TPU v5 lite"
    bytes_bound = W.Work(flops=1.0, bytes=819e9)
    pct, bound = W.share(bytes_bound, 2.0, kind)
    assert bound == "bytes" and pct == pytest.approx(50.0)
    flops_bound = W.Work(flops=197e12, bytes=1.0)
    pct, bound = W.share(flops_bound, 1.0, kind)
    assert bound == "flops" and pct == pytest.approx(100.0)


def test_peak_table_names_its_source_and_refuses_unknown_kinds():
    p = W.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        W.peaks("cpu")
    with pytest.raises(KeyError):
        W.least_time(W.Work(1.0, 1.0), "TPU v4")
