"""The control: the reference one precision step down must fail the check.

The control is the reference with the MTTKRP's gathered rows and products
in bfloat16 (``reference.als_sweeps(..., ec_dtype=bfloat16)``), the step a
change to this bandwidth-bound kernel would be tempted to take. It is
compared with the float32 reference exactly as the program's first sweep
is, from the same tensor and initial factors, and must come out not
correct under each configuration's limits. On the chip it was read at the
cells' own sizes (PERF.md); here it runs at a small scale on the CPU.
"""
import jax.numpy as jnp
import pytest

import check
import gen
import reference
from conftest import tiny_cell

SCALE = 2e-4


@pytest.mark.parametrize("workload", ["amazon-r32.resident",
                                      "twitch-r32.resident"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_control_fails_the_check(workload, seed):
    c = tiny_cell(workload, SCALE).config
    shape = tuple(c["shape"])
    idx, val = gen.generate(seed, shape, c["draws"], c["zipf_a"])
    coo = reference.DeviceCOO(idx, val, shape)
    init = reference.init_factors(seed, shape, c["rank"])
    ref = reference.als_sweeps(coo, init, 1)[0]
    ctl = reference.als_sweeps(coo, init, 1, ec_dtype=jnp.bfloat16)[0]
    numbers = check.compare(ctl, ref)
    ok, checks = check.judge(numbers, c["limits"])
    assert not ok, checks
    # the reference against itself reads exactly nought
    same = check.compare(reference.als_sweeps(coo, init, 1)[0], ref)
    assert all(v == 0.0 for v in same.values())
