"""Scheduler, by the engine's own counters: the rows its programs carried
over the window (``live_rows``: live decode rows and the prefill row,
summed over program calls) over the calls (``steps``) times the slots,
from ``stats()`` at the window's two ends; None without them."""

from bench.readings import counter_delta


def read(run):
    steps = counter_delta(run, "steps")
    rows = counter_delta(run, "live_rows")
    if not steps or rows is None:
        return None
    return 100.0 * rows / (steps * run["occupancy_slots"])
