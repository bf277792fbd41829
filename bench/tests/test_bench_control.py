"""The control comes out not correct where the program comes out correct.

The control is the plain reference computed one precision below the
configuration's bfloat16: every matmul on float8 operands.  At a small size
on the CPU, over three seeds, the run's own verdict is correct and the same
verdict, given the tokens the control puts first at the same positions, is
not.  (The chip readings at each cell's own size, from which the cells'
limits were set, are in PERF.md.)"""
import time

import jax
import pytest

from bench.lib import harness
from bench.tests import tiny

LIMIT = 0.03


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(seed):
    cell = tiny.cell("open_loop", limits={"max_logit_gap": LIMIT,
                                          "min_tokens_compared": 1},
                     hidden_size=256, intermediate_size=768,
                     vocab_size=4096, head_dim=64)
    cell.mix = {**cell.mix, "check": {"requests": 8}}
    out = harness.run_cell(cell, seed, 1.0, False, jax.devices()[:1],
                           time.perf_counter(), "/nonexistent",
                           control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert not out["control"]["correct"], out["control"]
    assert out["control"]["checks"]["max_logit_gap"]["value"] > LIMIT
    assert list(out)[-1] == "checks"
