"""Engine host loop: the program-free milliseconds (no XLA module runs on
the chip) inside each decode tick's ``engine.step`` span, the mean over
the decode ticks of the traced window (``bench/engine_trace.py``
``exposed``); None without engine spans."""

from bench.readings import engine_exposed


def read(run):
    return engine_exposed(run, "host_exposed_ms.decode")
