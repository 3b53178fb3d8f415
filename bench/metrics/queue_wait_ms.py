"""Scheduler: median of admission (the engine's ``t_admit`` stamp, on the
same monotonic clock) minus when the request was due, over requests
admitted in the window."""

from bench.readings import in_window, percentile


def read(run):
    waits = [(lv.req.t_admit - lv.due) * 1e3 for lv in run["requests"]
             if lv.req.t_admit and in_window(run, lv.req.t_admit)]
    return percentile(waits, 50)
