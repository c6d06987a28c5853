"""CPU tests of the benchmark's own code (``python -m pytest bench/tests``).

They run the harness at tiny sizes with the Pallas kernels interpreted;
they never give a time or a device metric.
"""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def tiny_cell(name: str, small: bool = False) -> dict:
    """The cell as `BENCHMARK.json` finds it, at a size the interpreter
    runs in seconds; ``small`` is large enough for the bfloat16 control's
    rounding to show in the histograms' gains."""
    from harness import common
    cell = copy.deepcopy(common.load_cell(name))
    conf = cell["config"]
    if small:
        conf.update(n_train=4096, n_eval=1024, n_features=16, n_outputs=12)
        conf["gbdt"].update(depth=4, n_bins=64, use_kernel="interpret")
    else:
        conf.update(n_train=384, n_eval=96, n_features=6, n_outputs=7)
        conf["gbdt"].update(depth=3, n_bins=16, use_kernel="interpret")
    cell["traffic"].update(n_trees=4, split_check_rounds=2)
    return cell


@pytest.fixture
def tiny():
    return tiny_cell


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """CPU tests compile for the CPU: keep them out of the checkout's
    persistent compile cache, which is the chip's."""
    from harness import common
    monkeypatch.setattr(common, "enable_caches", lambda: "")
