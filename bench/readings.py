"""Helpers the metric readers share: stamps in the window, percentiles,
the device time of each engine program in a trace reduction, the
engine's exposed host time in it, and how far an engine counter moved
over the window."""

from __future__ import annotations

import numpy as np

MIXED = "jit_mixed_fn"
DECODE = "jit_decode_fn"
# kinds from bench.trace.classify: the ops that carry the projections
# (the weight slices that feed the GEMMs included), and paged attention
GEMM_OPS = ("pallas:gemm", "xla:dot", "xla:param_copy")
PAGED_ATTENTION = "pallas:paged_attention"


def in_window(run, t) -> bool:
    ws, we = run["window"]
    return ws <= t <= we


def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def module(run, name):
    """(calls, device seconds) of one engine program in the traced window."""
    m = (run["trace"] or {}).get("modules", {}).get(name)
    if not m or not m["calls"]:
        return None
    return m["calls"], m["seconds"]


def traced_calls(run, program):
    return [r for r in run["trace"]["calls"] if r["program"] == program]


def engine_exposed(run, name):
    """One of ``bench/engine_trace.py``'s ``exposed`` numbers over the
    traced window; None where the trace holds no engine spans."""
    return ((run.get("trace") or {}).get("engine") or {}).get(name)


def counter_delta(run, key):
    """How far the engine's ``stats()[key]`` moved between the window's
    two ends; None where the run kept no stats or the engine has no such
    counter."""
    stats = run.get("stats")
    if not stats or key not in stats[0] or key not in stats[1]:
        return None
    return stats[1][key] - stats[0][key]


def token_gaps_ms(run):
    """Every gap between consecutive output tokens of a request whose
    later token came in the window."""
    return [(b - a) * 1e3 for lv in run["requests"]
            for a, b in zip(lv.stamps, lv.stamps[1:]) if in_window(run, b)]


def idle_share(run):
    red = run["trace"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def mfu(run, name, program):
    """Useful FLOPs of the real tokens of ``program``'s calls
    (``bench/work.py``) over their device time at the chip's bf16 peak;
    per-call means over the traced window, so a call cut by the window's
    edge weighs nothing."""
    from bench import work
    m = module(run, name)
    calls = traced_calls(run, program)
    if m is None or not calls:
        return None
    fam, c = run["family"], run["config"]
    flops = sum(work.call_flops(fam, c, r) for r in calls) / len(calls)
    return 100.0 * flops / (m[1] / m[0] * run["peaks"]["bf16_flops_s"])
