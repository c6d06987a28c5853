"""The sketched cell, ``dionis.train``, against the plain reference on the
CPU at tiny sizes: the tiny outputs (7, or 12 at the control's size) stay
above ``sketch_k`` = 5, so the random projection is on and the reference
draws the same projection from the seed."""
import json
import time

import pytest

from harness import faults

NAME = "dionis.train"


def drive(cell, seed, trace=False, **kw):
    from harness import train
    return train.run(cell, seed=seed, seconds=0.5, trace=trace,
                     t0=time.perf_counter(), require_tpu=False, **kw)


def test_tiny_cell_is_sketched(tiny):
    conf = tiny(NAME)["config"]
    assert conf["gbdt"]["sketch_method"] == "random_projection"
    assert conf["gbdt"]["sketch_k"] < conf["n_outputs"]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(tiny, capsys, trace):
    from harness import common
    result, checks = drive(tiny(NAME), 2 ** 31 + 11, trace=trace)
    common.emit(result, checks)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    if not trace:
        assert {"setup_s", "train_rounds_per_s"} <= set(line["metrics"])


def test_control_bf16_histograms_fail(tiny):
    result, checks = drive(tiny(NAME, small=True), 2 ** 31 + 5,
                           cfg_overrides={"hist_dtype": "bfloat16"})
    assert result["correct"] is False, checks
    assert not checks["gain_gap"]["ok"]


def test_program_passes_at_the_control_size(tiny):
    result, checks = drive(tiny(NAME, small=True), 2 ** 31 + 5)
    assert result["correct"] is True, checks


@pytest.mark.parametrize("fault", faults.TRAIN_FAULTS)
def test_faults_fail(tiny, fault):
    with faults.planted(fault):
        result, checks = drive(tiny(NAME), 2 ** 31 + 5)
    assert result["correct"] is False, checks
