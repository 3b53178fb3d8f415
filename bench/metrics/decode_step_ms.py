"""Model step: device time per call of the decode program, the XLA module
of ``decode_fn``, in the traced window."""

from bench.readings import DECODE, module


def read(run):
    m = module(run, DECODE)
    return None if m is None else 1e3 * m[1] / m[0]
