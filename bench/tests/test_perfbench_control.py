"""Each cell's control fails its correctness limit: the plain reference
computed one precision below the configuration's (bfloat16 for
float32), put in the program's place, at a size a CPU test holds.  The
same comparison passes the program."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_util import cells, run_module, small_cell

run = run_module()


@pytest.mark.parametrize("workload", list(cells()))
def test_control_fails_the_limit(workload):
    cell_def, config, traffic = small_cell(workload)
    cell = run.entry_module(traffic["entry"]).Cell(
        config, traffic, 5000000011, int(cell_def["chips"]))
    (name, limit), = traffic["check"]["limits"].items()
    out = cell.call()
    ref = cell.reference(np.float32)
    program = cell.number(cell.select(out), ref)
    assert program <= limit, (name, program, limit)
    control = cell.number(cell.reference(jnp.bfloat16), ref)
    assert control > limit, (name, control, limit)
