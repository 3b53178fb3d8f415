"""Kernel: the live K/V bytes the decode calls must read (each live slot's
cached tokens over every layer, ``bench/work.py``) at the chip's memory
bandwidth, over the device time of the fused paged-attention kernel in
the decode program; per-call means over the traced window."""

from bench import trace, work
from bench.readings import DECODE, PAGED_ATTENTION, traced_calls


def read(run):
    red = run["trace"]
    spans = red["module_calls"].get(DECODE)
    calls = traced_calls(run, "decode")
    if not spans or not calls:
        return None
    seconds = trace.op_seconds(red, spans,
                               lambda op: op[0] == PAGED_ATTENTION)
    if seconds <= 0:
        return None
    nbytes = sum(work.kv_read_bytes(run["family"], run["config"], r["decode"])
                 for r in calls) / len(calls)
    least = nbytes / run["peaks"]["hbm_bytes_s"]
    return 100.0 * least / (seconds / len(spans))
