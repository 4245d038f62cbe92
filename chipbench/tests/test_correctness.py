"""``correct`` at the tiny size on the CPU: a sound run reads correct, and
with the control or a fault planted under a training cell's timed path
(``faults.py``) the cell's own driver and comparison read not correct,
against limits set by the cells' rule from tiny readings
(``limits_tiny.json``).  The cells' own limits, and the readings at their
size they were set from, come from ``run.py`` and
``tools/control_train.py`` on the chip."""

import jax
import pytest

from chipbench.faults import FAULTS
from chipbench.tests.conftest import run_tiny

TRAINING = ["mamba2-370m.train-steady", "mamba2-370m.train-restart"]


@pytest.mark.parametrize("workload", TRAINING)
@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_only_the_sound_program_reads_correct(workload, fault):
    jax.clear_caches()
    out = run_tiny(workload, fault=fault)
    assert out["correct"] is (fault is None), out["checks"]
