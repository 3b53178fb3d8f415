#!/usr/bin/env python3
"""The engine's host spans beside the device trace: what the host does
while the chip waits.

``PagedEngine.step`` writes one ``engine.step`` span per tick on the
profiler's clock, its parts as child spans: ``engine.schedule`` (expiry,
watchdog, admission with one ``engine.admit`` per request seated),
``engine.inputs``, ``engine.dispatch.<program>`` (with an
``engine.compile.<program>`` inside when the call compiles),
``engine.sample``, ``engine.advance`` (one ``engine.finish`` per retired
request) and ``engine.push_tables``.  :func:`bench.trace.extract` keeps
them beside the harness's spans.  From its record

* :func:`host_idle` splits the window's program-free time (no XLA module
  runs on any device) over the innermost host span covering each
  instant, so a gap that crosses two spans is shared between them;
* :func:`exposed` gives, per decode tick (an ``engine.step`` inside the
  window that holds an ``engine.dispatch.decode``), the program-free
  milliseconds of the whole tick (``host_exposed_ms.decode``), of its
  preparation (``engine.schedule``, ``engine.inputs`` and
  ``engine.dispatch.decode``: ``prepare_exposed_ms.decode``) and of its
  sampling (``sample_exposed_ms.decode``); None without engine spans.

Run on a trace that ``bench/run.py --trace 1 --keep-trace DIR`` kept:

    python3 bench/engine_trace.py DIR [--record OUT.json.gz --ticks 3]
                                     [--op-texts OUT.json]

prints one JSON object; ``--record`` also writes the window's first
decode ticks as a small record for the tests, ``--op-texts`` the HLO text
of one device op of each kind.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import statistics
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import trace  # noqa: E402
from bench.readings import DECODE  # noqa: E402

STEP = "engine.step"
DISPATCH_DECODE = "engine.dispatch.decode"
PREPARE = ("engine.schedule", "engine.inputs", DISPATCH_DECODE)
SAMPLE = ("engine.sample",)
NO_SPAN = "host:other"


def program_free(ex: dict, t0: float, t1: float) -> list:
    """The ``[start, end)`` intervals of ``[t0, t1]`` in which no XLA
    module runs on any device."""
    busy = trace.union([(max(s, t0), min(s + d, t1))
                        for dev in ex["devices"].values()
                        for _, s, d in dev["modules"]
                        if s < t1 and s + d > t0])
    free, edge = [], t0
    for s, e in busy:
        if s > edge:
            free.append((edge, s))
        edge = max(edge, e)
    if edge < t1:
        free.append((edge, t1))
    return free


def free_within(free: list, a: float, b: float) -> float:
    """Nanoseconds of the sorted, disjoint intervals ``free`` inside
    ``[a, b]``."""
    total = 0.0
    for s, e in free[max(0, bisect.bisect_left(free, (a,)) - 1):]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def host_idle(ex: dict, t0: float | None = None,
              t1: float | None = None) -> dict:
    """Seconds of program-free time in ``[t0, t1]`` (by default the traced
    window) per host span name, each instant given to the innermost
    (shortest) span covering it, ``host:other`` where none does."""
    if t0 is None:
        t0, t1 = trace.traced_window(ex)
    spans = sorted((s for s in ex["spans"] if s[0] != trace.WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out: dict = {}
    for a, b in program_free(ex, t0, t1):
        near = [s for s in spans[:bisect.bisect_left(starts, b)]
                if s[1] + s[2] > a]
        cuts = sorted({a, b} | {x for s in near for x in (s[1], s[1] + s[2])
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            cover = [s for s in near if s[1] <= mid <= s[1] + s[2]]
            name = min(cover, key=lambda s: s[2])[0] if cover else NO_SPAN
            out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def decode_ticks(ex: dict, t0: float, t1: float) -> list:
    """``(tick, children)`` of every ``engine.step`` inside ``[t0, t1]``
    that holds an ``engine.dispatch.decode``; its children are the engine
    spans that start inside it."""
    spans = sorted((s for s in ex["spans"] if s[0].startswith("engine.")),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out = []
    for tick in spans:
        if tick[0] != STEP or not (t0 <= tick[1]
                                   and tick[1] + tick[2] <= t1):
            continue
        kids = [s for s in spans[bisect.bisect_left(starts, tick[1]):
                                 bisect.bisect_right(starts,
                                                     tick[1] + tick[2])]
                if s[0] != STEP]
        if any(s[0] == DISPATCH_DECODE for s in kids):
            out.append((tick, kids))
    return out


def tick_parts(ex: dict, t0: float, t1: float) -> list:
    """Per decode tick, program-free nanoseconds of the whole
    ``engine.step`` (``host``), of the spans that prepare the program
    (``prepare``) and of sampling (``sample``)."""
    free = program_free(ex, t0, t1)

    def part(kids, names):
        return sum(free_within(free, s[1], s[1] + s[2])
                   for s in kids if s[0] in names)

    return [{"host": free_within(free, t[1], t[1] + t[2]),
             "prepare": part(kids, PREPARE), "sample": part(kids, SAMPLE)}
            for t, kids in decode_ticks(ex, t0, t1)]


def exposed(ex: dict, t0: float | None = None,
            t1: float | None = None) -> dict:
    """Program-free milliseconds per decode tick, the mean over decode
    ticks: of the whole ``engine.step``, of the spans that prepare the
    program, and of sampling; every value None when the window holds no
    decode tick (a trace without engine spans)."""
    if t0 is None:
        t0, t1 = trace.traced_window(ex)
    parts = tick_parts(ex, t0, t1)
    n = len(parts)

    def mean_ms(key):
        return sum(p[key] for p in parts) / n * 1e-6 if n else None

    return {"decode_ticks": n,
            "host_exposed_ms.decode": mean_ms("host"),
            "prepare_exposed_ms.decode": mean_ms("prepare"),
            "sample_exposed_ms.decode": mean_ms("sample")}


def record(ex: dict, ticks: int) -> dict:
    """The first ``ticks`` decode ticks of the window, every event that
    starts inside them, the harness's spans that overlap them, and a
    ``bench.traced`` span over exactly those ticks."""
    t0, t1 = trace.traced_window(ex)
    picked = decode_ticks(ex, t0, t1)[:ticks]
    a = picked[0][0][1]
    b = picked[-1][0][1] + picked[-1][0][2]

    def inside(ev):
        return a <= ev[-2] <= b

    out = {"devices": {}, "spans": [[trace.WINDOW_SPAN, a, b - a]]}
    for name, dev in ex["devices"].items():
        out["devices"][name] = {
            "modules": [m for m in dev["modules"] if inside(m)],
            "ops": [o for o in dev["ops"] if inside(o)]}
    out["spans"] += [s for s in ex["spans"] if s[0] != trace.WINDOW_SPAN
                     and s[1] < b and s[1] + s[2] > a]
    out["spans"].sort(key=lambda s: s[1])
    return out


def summary(ex: dict) -> dict:
    """What the engine's spans say of one trace, beside the numbers
    :func:`bench.trace.reduce` gives: the exposed times (means, and
    medians over decode ticks), the split of program-free time, the
    period between decode calls back to back, compiles in the window
    and the idle gaps named by span."""
    red = trace.reduce(ex)
    t0, t1 = red["t0_ns"], red["t1_ns"]
    free = program_free(ex, t0, t1)
    parts = tick_parts(ex, t0, t1)
    starts = sorted(s for s, _ in red["module_calls"].get(DECODE, []))
    return {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_s": red["window_s"] - red["busy_s"],
        "program_free_s": sum(e - s for s, e in free) * 1e-9,
        **exposed(ex, t0, t1),
        "median_ms": {k: statistics.median(p[k] for p in parts) * 1e-6
                      for k in ("host", "prepare", "sample")}
        if parts else None,
        "decode_exposed_s": sum(p["host"] for p in parts) * 1e-9,
        "decode_period_ms": statistics.median(
            b - a for a, b in zip(starts, starts[1:])) * 1e-6
        if len(starts) > 1 else None,
        "compile_spans": sum(s[0].startswith("engine.compile.")
                             for s in ex["spans"] if t0 <= s[1] <= t1),
        "host_idle_ms": {k: v * 1e3
                         for k, v in host_idle(ex, t0, t1).items()},
        "idle_gaps": red["idle_gaps"],
    }


def op_texts(path: str) -> dict:
    """``{kind: HLO text}``: the first synchronous device op of each kind
    that :func:`bench.trace.classify` reads in the trace file ``path``."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not trace.device_plane(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace.OP_LINES[0]:
                for e in line.events:
                    out.setdefault(trace.classify(e.name), e.name)
    return dict(sorted(out.items()))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", help="a kept trace directory or .xplane.pb")
    p.add_argument("--record", help="write the first decode ticks here")
    p.add_argument("--ticks", type=int, default=3)
    p.add_argument("--op-texts", help="write one op text of each kind here")
    args = p.parse_args(argv)
    path = args.trace
    if Path(path).is_dir():
        path = sorted(glob.glob(str(Path(path) / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
    ex = trace.extract(path)
    if args.record:
        with gzip.open(args.record, "wt") as f:
            json.dump(record(ex, args.ticks), f)
    if args.op_texts:
        Path(args.op_texts).write_text(json.dumps(op_texts(path), indent=1))
    print(json.dumps(summary(ex)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
