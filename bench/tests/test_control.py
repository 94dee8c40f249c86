"""The control: the reference computed in bfloat16, put in the
program's place, comes out not correct against each cell's limit, while
the program comes out correct (tiny sizes, on the CPU)."""
import jax
import pytest

from bench import calibrate, registry
from bench.tests import tiny


@pytest.mark.parametrize("cell", ["heat2d-16384.loop"])
def test_control_fails_the_limit(cell):
    limit = registry.data("cells", cell)["limits"]["rel_err"]
    lines = list(calibrate.readings(cell, [3, 2**32 + 3], 1.0, jax.devices(),
                                    config=tiny.config(cell)))
    for line in lines:
        assert line["program"] <= limit < line["control"], line
