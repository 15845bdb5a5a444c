"""The control of `correct`: the plain reference in the program's place,
at int8 weights (per output channel), a precision below the
configuration's bf16, read by the same numbers and judged by the same
verdict, comes out not correct, while the program passes.  At a test
size on the CPU; bench/control.py reads the same on the chip at the
cell's size."""
import time

import jax

from conftest import tiny_spec
from harness import cell


def test_int8_control_is_not_correct():
    spec = tiny_spec()
    res, rows = cell.run(spec, 3, 2.0, False, time.perf_counter(),
                         jax.devices(), quant=("int8",))
    assert res["correct"], rows              # the program itself passes
    ctl = res["control"]["int8"]
    assert ctl["correct"] is False, (ctl, spec["cell"]["limits"])
