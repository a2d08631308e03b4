"""A run with the timed path broken underneath must come out not
``correct``: the harness's look for a chip is skipped and the rest of a run
is driven on the CPU at a small size, once sound and once for each fault
of ``faults.py``, through the batched engine and, with sites of mixed
feature counts, through the cohort engine."""
import jax
import pytest

import faults
import run as RUN
from harness_cell import CELLS, MIXED, small_cell

VARIANTS = [(c, None) for c in CELLS] + [(CELLS[0], MIXED)]


def _run(cell):
    return RUN.run_cell(cell, 2**31 + 5, 0.0, False, jax.devices(),
                        log=lambda s: None)


@pytest.mark.parametrize("name,nf_choices", VARIANTS)
def test_sound_run_is_correct(name, nf_choices):
    out = _run(small_cell(name, nf_choices))
    assert out["correct"], out["compared"]
    assert out["failed"] == 0
    assert list(out)[-1] == "compared"


# at the small mixed size fewer than half the clients improve in the second
# epoch, so keeping the first epoch's best parameters moves no median there
CASES = [(c, n, f) for c, n in VARIANTS for f in faults.NAMES
         if not (n and f == "best_stuck")]


@pytest.mark.parametrize("name,nf_choices,fault", CASES)
def test_fault_is_caught(name, nf_choices, fault):
    with faults.planted(fault):
        out = _run(small_cell(name, nf_choices))
    assert not out["correct"], out["compared"]
