"""The harness end to end on the CPU at tiny sizes: control flow, the
check, and a last line that parses."""
import json
import time

import pytest


def run(cell, trace, capsys, **kw):
    from harness import common, train
    result, checks = train.run(cell, seed=2 ** 31 + 11, seconds=0.5,
                               trace=trace, t0=time.perf_counter(),
                               require_tpu=False, **kw)
    common.emit(result, checks)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("name", ["otto.train"])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(tiny, capsys, name, trace):
    line = run(tiny(name), trace, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        # no device trace on the CPU: no device metric is reported
        assert not any(k.endswith(("roofline.train", "mfu.train",
                                   "kernel_ms.train", "idle.train"))
                       for k in line["metrics"])
    else:
        assert "setup_s" in line["metrics"]
