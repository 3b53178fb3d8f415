"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run of a small copy of the cell on the CPU, with the cell's own sampling
of requests to check, and with one fault planted in the engine it drives: a step that returns its state unchanged, half of the
slots' rows left out (their logits taken from the others), a served token
altered where it is produced.  The exchange between chips does not exist
in these one-chip cells.  A sound run of the same copy is correct.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

RATE = 30.0        # requests/s: every slot of the small engine in use
CELLS = [("yi6b.decode", "yi-6b")]


class _Wrap:
    """Stands in for one of the engine's compiled programs."""

    def __init__(self, program, after):
        self.program, self.after = program, after

    def __getattr__(self, name):
        return getattr(self.program, name)

    def __call__(self, params, pools, *args):
        return self.after(pools, self.program(params, pools, *args))


def state_unchanged(eng):
    class Frozen(_Wrap):
        def __call__(self, params, pools, *args):
            before = jax.tree.map(jnp.copy, pools)
            out = self.program(params, pools, *args)
            return (*out[:-1], before)

    for name in ("_prefill", "_decode"):
        setattr(eng, name, Frozen(getattr(eng, name), None))


def half_batch_left_out(eng):
    half = eng.slots // 2

    def rows(x):
        return jnp.concatenate([x[:half], x[:x.shape[0] - half]])
    eng._prefill = _Wrap(eng._prefill,
                         lambda p, out: (rows(out[0]),) + tuple(out[1:]))
    eng._decode = _Wrap(eng._decode,
                        lambda p, out: (rows(out[0]),) + tuple(out[1:]))


def token_altered(eng):
    sample, calls = eng._sample, [0]

    def altered(logits):
        nxt = np.array(sample(logits))
        calls[0] += 1
        if calls[0] % 7 == 0:
            nxt[:] = (nxt + 1) % logits.shape[-1]
        return nxt
    eng._sample = altered


def _run(workload, name, fault, seed=2 ** 32 + 11):
    bench, cell, _ = harness.find_cell(workload)
    c = tiny.config(name)
    return harness.run(workload, seed, 3.0, False, t_process=time.monotonic(),
                       require_chip=False, config=c,
                       mix=tiny.mix(cell["traffic"]), rate=RATE, fault=fault)


@pytest.mark.parametrize("workload,name", CELLS)
def test_sound_run_is_correct(workload, name):
    r = _run(workload, name, None)
    assert r["correct"], r["compared"]
    slots = tiny.config(name)["engine"]["slots"]
    assert r["info"]["checked_slots"] == list(range(slots))
    assert r["info"]["window_compiles"] == 0
    assert r["info"]["new_programs"] == [0, 0, 0]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
@pytest.mark.parametrize("workload,name", CELLS)
def test_fault_is_not_correct(workload, name, fault):
    r = _run(workload, name, fault)
    assert not r["correct"], r["compared"]
