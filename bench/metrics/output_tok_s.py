"""Output tokens delivered in the window over its length, on the harness
clock; tokens of requests still in flight count."""

from bench.readings import in_window


def read(run):
    ws, we = run["window"]
    n = sum(1 for lv in run["requests"] for t in lv.stamps
            if in_window(run, t))
    return n / (we - ws)
