"""The control: the plain reference computed in float8, one precision step
below the configuration's bfloat16, put in the program's place, fails the
comparison at a size the CPU holds, on each seed. On the chip the same
readings at the cell's own size set the upper ends of the limits
(``bench/readings.py``)."""

import pytest

from harness import compare, periodic, training

import smoke


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_float8_reference_in_the_programs_place_fails(tmp_path, seed):
    cell = smoke.smoke_cell("phi3-mini-3l.periodic")
    template, prog = periodic.program_readings(
        cell, seed, root=str(tmp_path), cache_dir=None)
    ref = training.reference(cell, seed, template)
    sound = compare.gaps(prog, ref)
    control = compare.gaps(training.reference(cell, seed, template,
                                              quant="fp8"), ref)
    limits = cell.limits
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert any(control[k] > limits[k] for k in control), control
