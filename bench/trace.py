"""From a JAX profiler trace to the numbers per-layer metrics read.

Two steps, kept apart so the second can be tested on a small recorded
trace without the profiler:

* :func:`extract` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
  and keeps three kinds of event on the profiler's common clock: the XLA
  module calls of each device plane ``[name, start_ns, duration_ns]``, its
  ops, synchronous and asynchronous, ``[kind, start_ns, duration_ns]``
  with the kind that :func:`classify` reads from the op's HLO text, and
  the host spans of the harness (``bench.*``) and of the engine
  (``engine.*``).
* :func:`reduce` takes that record and the traced window (the harness's
  ``bench.traced`` span) and gives: device busy time (the union of op
  intervals, asynchronous copies included, averaged over devices), device
  time per module, time per kind of op (each op's own time: loops and
  asynchronous copies, whose bodies and waits are listed apart, left
  out), and the longest idle gaps, each named by the innermost host span
  it fell in.
"""

from __future__ import annotations

import re
from collections import defaultdict

SPAN_PREFIXES = ("bench.", "engine.")
WINDOW_SPAN = "bench.traced"
MODULES_LINE = "XLA Modules"
OP_LINES = ("XLA Ops", "Async XLA Ops")
# kinds left out of the time per kind: asynchronous copies overlap the ops
# that wait for them, and a loop's or call's body ops are listed too
NESTING = ("async:", "xla:while", "xla:conditional", "xla:call")

# the kind of a Pallas kernel whose name (``pl.pallas_call(name=...)``)
# the readers know by another; every other kernel is ``pallas:<name>``
KERNELS = {"kraken_gemm": "pallas:gemm",
           "paged_decode_attention": "pallas:paged_attention"}

_BASE = re.compile(r"%([A-Za-z_\-]+?)[.\d]* = ")
_TARGET = re.compile(r'custom-call\(.*?\), custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k\w+)")


def kernel_kind(name: str) -> str:
    """The kind of the Pallas kernel named ``name``."""
    return KERNELS.get(name, f"pallas:{name}")


def classify(text: str, asynchronous: bool = False) -> str:
    """What a device op is, from its HLO text in the trace.  A Pallas
    kernel (``tpu_custom_call``) is known by its instruction's base name,
    the name ``pl.pallas_call(name=...)`` gave it (the enclosing
    function's where it has none), through :func:`kernel_kind`.
    An XLA op that reads the program's parameters (``%params...``, the
    per-layer weight slices that feed the GEMMs) is ``xla:param_copy``,
    one that copies the KV pools (``%pools...``) ``xla:pool_copy``; any
    other is its instruction's base name, a fusion with its kind; dot and
    convolution fusions (``kOutput``) are ``xla:dot``."""
    m = _BASE.match(text)
    base = m.group(1) if m else text.split(" ")[0]
    if asynchronous:
        return f"async:{base}"
    target = _TARGET.search(text)
    if target:
        if target.group(1) != "tpu_custom_call":
            return f"xla:custom-call:{target.group(1)}"
        return kernel_kind(base)
    args = text.split(" = ", 1)[-1]
    if "%params" in args:
        return "xla:param_copy"
    if "%pools" in args and "copy" in base:
        return "xla:pool_copy"
    kind = _KIND.search(text)
    if ("convolution" in base or base.startswith("dot")
            or (kind and kind.group(1) == "kOutput")):
        return "xla:dot"
    if base.endswith("fusion") and kind:
        return f"xla:fusion({kind.group(1)})"
    return f"xla:{base}"


def extract(path: str) -> dict:
    """The events of one trace file that the reduction needs."""
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes)


def device_plane(name: str) -> bool:
    """Whether a trace plane is an accelerator's."""
    return (name.startswith("/device:") and "CPU" not in name
            and "CUSTOM" not in name)


def from_planes(planes) -> dict:
    """:func:`extract` of the planes of a ``ProfileData``: device planes
    give modules and ops, host planes the spans named ``bench.*`` or
    ``engine.*``; every other host event is dropped."""
    out = {"devices": {}, "spans": []}
    for plane in planes:
        if device_plane(plane.name):
            dev = {"modules": [], "ops": []}
            kinds: dict = {}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
                elif line.name in OP_LINES:
                    asynchronous = line.name != OP_LINES[0]
                    for e in line.events:
                        key = (e.name, asynchronous)
                        if key not in kinds:
                            kinds[key] = classify(e.name, asynchronous)
                        dev["ops"].append([kinds[key], e.start_ns,
                                           e.duration_ns])
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["spans"] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIXES)]
    out["spans"].sort(key=lambda s: s[1])
    return out


def union(intervals):
    """Merge ``(start, end)`` intervals; returns the merged, sorted list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def module_base(name: str) -> str:
    """``jit_decode_fn(3731250012162501339)`` -> ``jit_decode_fn``."""
    return re.sub(r"\(.*\)$", "", name).strip()


def traced_window(ex: dict):
    """The ``bench.traced`` span the harness holds open over the window it
    traces, as ``(t0_ns, t1_ns)`` on the trace clock."""
    for name, s, d in ex["spans"]:
        if name == WINDOW_SPAN:
            return s, s + d
    return None


def reduce(ex: dict, t0_ns: float | None = None, t1_ns: float | None = None,
           *, n_gaps: int = 10) -> dict:
    """Reduce an :func:`extract` record over ``[t0_ns, t1_ns]`` on the
    trace clock; by default the ``bench.traced`` span, else the first to
    the last device op."""
    devices = ex["devices"]
    all_ops = [op for d in devices.values() for op in d["ops"]]
    if not all_ops:
        raise ValueError("the trace holds no device operation")
    if t0_ns is None and traced_window(ex) is not None:
        t0_ns, t1_ns = traced_window(ex)
    if t0_ns is None:
        t0_ns = min(op[1] for op in all_ops)
    if t1_ns is None:
        t1_ns = max(op[1] + op[2] for op in all_ops)

    def inside(s, d):
        return t0_ns <= s and s + d <= t1_ns

    busy_total = 0.0
    gaps = []
    modules = defaultdict(lambda: [0, 0.0])
    module_calls = defaultdict(list)
    kinds = defaultdict(float)
    for dev in devices.values():
        iv = [(max(s, t0_ns), min(s + d, t1_ns)) for _, s, d in dev["ops"]]
        merged = union([(s, e) for s, e in iv if e > s])
        busy_total += sum(e - s for s, e in merged)
        edge = t0_ns
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if edge < t1_ns:
            gaps.append((edge, t1_ns))
        for name, s, d in dev["modules"]:
            if inside(s, d):
                m = modules[module_base(name)]
                m[0] += 1
                m[1] += d
                module_calls[module_base(name)].append([s, s + d])
        for kind, s, d in dev["ops"]:
            if inside(s, d) and not kind.startswith(NESTING):
                kinds[kind] += d
    spans = [s for s in ex["spans"] if s[0] != WINDOW_SPAN]

    def host_span(t):
        covering = [s for s in spans if s[1] <= t <= s[1] + s[2]]
        return (min(covering, key=lambda s: s[2])[0] if covering
                else "host:other")

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "t0_ns": t0_ns, "t1_ns": t1_ns,
        "window_s": (t1_ns - t0_ns) * 1e-9,
        "busy_s": busy_total / max(1, len(devices)) * 1e-9,
        "modules": {k: {"calls": v[0], "seconds": v[1] * 1e-9}
                    for k, v in modules.items()},
        "module_calls": dict(module_calls),
        "top_ops": [[k, v * 1e-9]
                    for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[host_span((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:n_gaps]],
        "ops_in_window": [op for d in devices.values() for op in d["ops"]
                          if inside(op[1], op[2])],
    }


def op_seconds(red: dict, module_calls: list, match) -> float:
    """Device seconds of the ops that ``match(op)`` accepts and that start
    inside a call of one of ``module_calls`` (``[start, end]`` ns)."""
    total = 0.0
    spans = sorted(module_calls)
    j = 0
    for op in sorted(red["ops_in_window"], key=lambda o: o[1]):
        while j < len(spans) and spans[j][1] < op[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[1] and match(op):
            total += op[2]
    return total * 1e-9
