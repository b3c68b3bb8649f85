"""The control fails the comparison; its sound twin passes.

The control is the plain reference with bfloat16 distance products put in
the program's place (`snnbench.control`).  On the chip it runs at each
cell's own size through ``bench/control.py``; here at a tiny size on the
CPU.
"""
import pytest

from conftest import tiny
from snnbench import control


@pytest.mark.parametrize("name", ["sift1m.radius-open", "sift1m.join-backlog",
                                  "glove100.knn-open"])
def test_bf16_control_fails_and_f32_passes(name):
    ov = tiny(name)
    low = control.numbers(name, 6000000011, 1.0, bf16=True, overrides=ov)
    assert low["correct"] is False
    assert low["checks"]["dist_gap"]["value"] > \
        3 * low["checks"]["dist_gap"]["limit"]
    sound = control.numbers(name, 6000000011, 1.0, bf16=False, overrides=ov)
    assert sound["correct"] is True
    assert sound["checks"]["wrong_rows"]["value"] == 0
