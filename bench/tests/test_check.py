"""The check that decides ``correct`` fails the control and every planted
fault: runs of the harness on the CPU at tiny sizes, with the chip look
skipped and the timed path broken underneath."""
import time

import pytest

from harness import faults


def drive(cell, **kw):
    from harness import train
    return train.run(cell, seed=2 ** 31 + 5, seconds=0.5, trace=False,
                  t0=time.perf_counter(), require_tpu=False, **kw)


@pytest.mark.parametrize("name", ["otto.train"])
def test_train_control_bf16_histograms_fail(tiny, name):
    result, checks = drive(tiny(name, small=True),
                           cfg_overrides={"hist_dtype": "bfloat16"})
    assert result["correct"] is False, checks
    assert not checks["gain_gap"]["ok"]


@pytest.mark.parametrize("name", ["otto.train"])
def test_train_program_passes_at_the_control_size(tiny, name):
    result, checks = drive(tiny(name, small=True))
    assert result["correct"] is True, checks


@pytest.mark.parametrize("fault", faults.TRAIN_FAULTS)
@pytest.mark.parametrize("name", ["otto.train"])
def test_train_faults_fail(tiny, name, fault):
    with faults.planted(fault):
        result, checks = drive(tiny(name))
    assert result["correct"] is False, checks
