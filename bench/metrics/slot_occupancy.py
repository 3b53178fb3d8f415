"""Scheduler: mean share of the engine's slots that carried a real token
in each program call of the window."""


def read(run):
    calls = [r for r in run["calls"] if r["program"] is not None]
    if not calls:
        return None
    slots = run["occupancy_slots"]
    return 100.0 * sum(r["live"] for r in calls) / (len(calls) * slots)
