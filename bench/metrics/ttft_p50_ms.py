"""Median time to first token, from when each request was due (its
scheduled arrival in an open loop, its submit in a closed loop) to the
return of the step that delivered its first token, over the requests whose
first token came in the window."""

from bench.readings import in_window, percentile


def read(run):
    ttft = [(lv.stamps[0] - lv.due) * 1e3 for lv in run["requests"]
            if lv.stamps and in_window(run, lv.stamps[0])]
    return percentile(ttft, 50)
