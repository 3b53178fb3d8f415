"""Engine host loop: the program-free milliseconds inside a decode tick's
preparation (``engine.schedule``, ``engine.inputs`` and
``engine.dispatch.decode``), the mean over the decode ticks of the
traced window (``bench/engine_trace.py`` ``exposed``); None without
engine spans."""

from bench.readings import engine_exposed


def read(run):
    return engine_exposed(run, "prepare_exposed_ms.decode")
