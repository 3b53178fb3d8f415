"""Whole step: the share of the chip's bf16 peak that the useful work of
the mixed (chunked-prefill) program's calls reaches (``readings.mfu``)."""

from bench.readings import MIXED, mfu


def read(run):
    return mfu(run, MIXED, "mixed")
