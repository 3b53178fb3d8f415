"""The correctness control comes out not correct, at a size a CPU test
run holds.

The control is the float32 reference with every GEMM in int8 (weights per
output column, activations per row): the step below the bfloat16 the
configurations state.  The harness judges it with the same comparison as
the program (``harness.compare``).  On the chip, at the cell's own size,
it fails the cell's limit (``bench/calibrate.py``; the readings and the
limit are in ``PERF.md``).  The cell's limit does not carry over to a
smaller copy: at these widths the int8 control reads 0.0033-0.0060 and the
program 0.0006-0.0013 (4 seeds each), so this copy holds a limit of its
own between the two.
"""

import time

import pytest

from bench import harness
from bench.tests import tiny

LIMIT = 0.002          # between the readings above, for this copy only


def medium():
    c = tiny.config("yi-6b")
    c.update(num_hidden_layers=16, hidden_size=512, intermediate_size=1024,
             vocab_size=64000, num_attention_heads=8, num_key_value_heads=2)
    c["engine"]["slots"] = 8
    c["correct"]["limits"] = {"mean_logit_gap": LIMIT}
    return c


def mix():
    m = tiny.mix("decode")
    m["clients"] = 8
    m["output_tokens"].update(median=24, min=12, max=48)
    return m


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 2])
def test_control_is_not_correct(seed):
    r = harness.run("yi6b.decode", seed, 4.0, False,
                    t_process=time.monotonic(), require_chip=False,
                    config=medium(), mix=mix(), rate=30.0, control=True)
    info = r["info"]
    assert len(info["checked_slots"]) >= 4
    assert r["correct"], r["compared"]
    assert not info["control_correct"], info["control_compared"]
