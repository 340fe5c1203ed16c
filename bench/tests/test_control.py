"""The control: the plain reference computed one precision lower
(bfloat16 for the program's float32) put in the program's place must come
out as not correct, while the program itself comes out correct.  At test
size on the CPU; on the chip at the cells' own sizes, ``bench/calibrate.py``
reads the same numbers over many seeds."""
import ml_dtypes
import pytest

from lib import harness

CELLS = ["lustre248x4096.online_filebench",
         "lustre248x4096.replay_filebench",
         "lustre248x4096.sweep_filebench"]


def drive(cell, seed, steps=10):
    harness.import_program()
    mode = harness.load_module("modes", cell.traffic["mode"],
                               cell.root).Mode(cell, seed, harness.Spans())
    for _ in range(steps):
        mode.step()
    return mode


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(small, name):
    _, resolve = small
    mode = drive(resolve(name), seed=2 ** 31 + 3)
    program = mode.check()
    assert program and all(c.ok for c in program), program
    control = mode.check(dtype=ml_dtypes.bfloat16)
    assert not all(c.ok for c in control), control
