"""The control of `correct`: the reference computed with three-pass
bfloat16 products, put in the program's place, must fail the limits that
the configurations state by the harness's own verdict, while the reference
at float32 HIGHEST passes them.  At a CPU size, with the passes written
out, since a CPU has no HIGH; bench/control.py runs the platform's HIGH at
the cells' sizes on the chip."""

import jax
import pytest

import control
import reference
import traffic
from helpers import tiny_config


@pytest.mark.parametrize("model", [1, 4])
@pytest.mark.parametrize("mix", ["learn.backlog", "code.rate"])
def test_three_pass_control_is_not_correct(mix, model):
    cfg = tiny_config(model, m=256, atoms=2048)
    nums = control.control_numbers(cfg, traffic.load(mix), 2**31 + 5, jax.devices()[:model],
                                   matmul="3pass")
    assert not reference.verdict(reference.judge(nums, cfg["limits"])), nums


@pytest.mark.parametrize("mix", ["learn.backlog", "code.rate"])
def test_highest_reference_in_the_programs_place_is_correct(mix):
    cfg = tiny_config(1, m=256, atoms=2048)
    nums = control.control_numbers(cfg, traffic.load(mix), 2**31 + 5, jax.devices()[:1],
                                   matmul="highest")
    assert reference.verdict(reference.judge(nums, cfg["limits"])), nums
