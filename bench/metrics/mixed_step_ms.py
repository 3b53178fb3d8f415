"""Model step: device time per call of the mixed (chunked-prefill) program,
the XLA module of ``mixed_fn``, in the traced window."""

from bench.readings import MIXED, module


def read(run):
    m = module(run, MIXED)
    return None if m is None else 1e3 * m[1] / m[0]
