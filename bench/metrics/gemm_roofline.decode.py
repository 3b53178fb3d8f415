"""Kernel: the least time of a decode call's projections (every layer's
GEMMs and the head at M = slots, each bound by FLOPs or by bytes,
``bench/work.py``) over the device time of the ops that carry them inside
the decode program: the Pallas GEMM kernel and the copies that slice each
layer's weights out of the stacked parameters for it, or XLA's dot
fusions where a later program computes them so (``bench/trace.py``'s
``classify``)."""

from bench import trace, work
from bench.readings import DECODE, GEMM_OPS


def read(run):
    red = run["trace"]
    spans = red["module_calls"].get(DECODE)
    if not spans:
        return None
    seconds = trace.op_seconds(red, spans, lambda op: op[0] in GEMM_OPS)
    if seconds <= 0:
        return None
    least = work.gemm_least_seconds(run["family"], run["config"],
                                    run["config"]["engine"]["slots"],
                                    run["peaks"])
    return 100.0 * least * len(spans) / seconds
