"""Whole step: the share of the chip's bf16 peak that the useful work of
the decode program's calls reaches (``readings.mfu``)."""

from bench.readings import DECODE, mfu


def read(run):
    return mfu(run, DECODE, "decode")
