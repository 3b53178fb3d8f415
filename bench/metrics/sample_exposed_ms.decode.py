"""Sampling: the program-free milliseconds inside a decode tick's
``engine.sample`` span (the sampling dispatch and the blocking readback
of the ids), the mean over the decode ticks of the traced window
(``bench/engine_trace.py`` ``exposed``); None without engine spans."""

from bench.readings import engine_exposed


def read(run):
    return engine_exposed(run, "sample_exposed_ms.decode")
