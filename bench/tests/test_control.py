"""The control comes out as not correct.

The control is the reference put in the program's place, one precision
step below the configuration's: three bfloat16 passes (`refops` "high")
for float32 at HIGHEST. It runs here on the CPU at each configuration's
full widths, on eight images in requests of each cell's traffic, and is
judged against the cell's limits by the harness's own comparison.
"""
import functools

import jax
import numpy as np
import pytest

from bench import harness, loadgen

CELLS = ("sf4-256.cifar.b128", "vgg11.cifar.b128", "sf4-256.cifar.b1",
         "vgg11.cifar.b1")


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = harness.load_cell(name)
    batch = min(cell.mix["batch"], 4)
    cell.mix.update(batch=batch, pool_requests=8 // batch)
    params = jax.jit(functools.partial(cell.model.init, cell.cfg))(
        harness.seed_key(2**33 + 17))
    x = loadgen.make_pool(cell.mix, 2**33 + 17)
    fwd = {p: jax.jit(functools.partial(cell.ref.forward, cell.cfg,
                                        precision=p))
           for p in ("highest", "high")}
    want, _ = fwd[cell.cfg["matmul_precision"]](params, x)
    control, _ = fwd["high"](params, x)
    readings = harness.compare(np.asarray(control), np.asarray(want))
    correct, checks = harness.judge(readings, cell.limits)
    assert cell.limits, f"{name} has no limits"
    assert not correct, checks
    # and the reference against itself is
    assert harness.judge(harness.compare(np.asarray(want), np.asarray(want)),
                         cell.limits)[0]
