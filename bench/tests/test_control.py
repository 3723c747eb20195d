"""The control of the correctness check, at sizes a test run can hold.

On the chip ``bench/control.py`` reads it at each cell's own size; here
the same code runs the tiny cells on the CPU under the real cells'
limits: the program's runs come out correct, and the float8 reference in
the program's place comes out not correct, through the same comparison.
"""

import pytest

from bench import control
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("name", sorted(tiny.TINY))
def test_float8_control_reads_incorrect(root, name):
    rows = control.readings(root, name, [1, 2**35], control_seeds=2,
                            require_tpu=False)
    program = [r for r in rows if not r["control"]]
    ctl = [r for r in rows if r["control"]]
    assert len(program) == len(ctl) == 2
    assert all(r["correct"] for r in program), program
    assert not any(r["correct"] for r in ctl), ctl
    # the control fails the number that every cell's limit separates
    for r in ctl:
        c = r["checks"]["logit_err"]
        assert c["value"] > c["limit"], r
