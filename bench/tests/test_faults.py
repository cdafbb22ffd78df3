"""Each cell's check refuses a run whose timed path is broken underneath
(``bench/faults.py``: the step returns its state unchanged, half of the
batch is left out, an answer is altered where it is produced), and a sound
run of each cell comes out correct.

The harness runs at its rehearsal sizes on the CPU (``rehearse=True``
skips only the look for a chip and the device metrics).
"""
import pytest

import faults
import run as harness

SEED = 2147483659
CELLS = {"covtype.train": "train_waves", "small2k.train": "fits",
         "covtype.serve": "serve_open"}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    res = harness.run(workload, SEED, 2.0, False, rehearse=True)
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("kind", faults.KINDS)
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_is_refused(workload, kind):
    with faults.planted(CELLS[workload], kind):
        res = harness.run(workload, SEED, 2.0, False, rehearse=True)
    assert not res["correct"], res["compared"]
