"""The control, the reference computed in bfloat16 and put in the
program's place, comes out not ``correct`` against the cell's limits; the
program comes out correct.  On the chip the same readings are taken at the
cells' own sizes with ``perfbench/calibrate.py``."""
import jax
import pytest

import calibrate
import compare
from harness_cell import CELLS, MIXED, small_cell


@pytest.mark.parametrize("name,nf_choices",
                         [(c, None) for c in CELLS] + [(CELLS[0], MIXED)])
def test_control_fails_and_program_passes(name, nf_choices):
    cell = small_cell(name, nf_choices)
    limits = cell["spec"]["limits"]
    judged = lambda r: {k: r[k] for k in limits}
    for seed in (3, 2**31 + 11):
        ctl = calibrate.control_reading(cell, seed)
        assert not compare.judge(judged(ctl), limits), ctl
    prog = calibrate.program_reading(cell, 7, jax.devices())
    assert compare.judge(judged(prog), limits), prog
