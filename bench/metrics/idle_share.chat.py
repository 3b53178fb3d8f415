"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of device-op intervals, asynchronous copies
included), in the cells that report ``itl_p99_ms``."""

from bench.readings import idle_share


def read(run):
    return idle_share(run)
