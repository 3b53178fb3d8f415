"""On-chip benchmark of the serving engine: one harness, driven by data.

See ``BENCHMARK.json`` at the repository root and ``bench/run.py``.
"""
