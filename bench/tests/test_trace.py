"""The trace reduction: busy union, device time per module, kernel
matching inside a module's calls, and idle gaps named by host spans; on
hand-made events and on a small trace recorded on a TPU v5e."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace as tr
from bench.readings import DECODE, GEMM_OPS, PAGED_ATTENTION

DATA = Path(__file__).resolve().parent / "data"


def op(kind, start, dur):
    return [kind, start, dur]


def test_hand_made_events():
    ex = {"devices": {"/device:TPU:0": {
        "modules": [["jit_decode_fn(7)", 100, 300],
                    ["jit_mixed_fn(8)", 600, 300]],
        "ops": [op("pallas:gemm", 100, 100),
                op("xla:fusion(kLoop)", 150, 100),            # overlaps
                op("pallas:paged_attention", 300, 100),
                op("xla:dot", 600, 300)]}},
        "spans": [["bench.traced", 0, 1000], ["bench.step", 60, 390],
                  ["bench.harvest", 450, 100], ["bench.step", 560, 400]]}
    red = tr.reduce(ex)
    assert (red["t0_ns"], red["t1_ns"]) == (0, 1000)
    # busy: [100, 250) + [300, 400) + [600, 900) = 150 + 100 + 300
    assert red["busy_s"] == pytest.approx(550e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["modules"]["jit_decode_fn"] == {"calls": 1,
                                               "seconds": pytest.approx(3e-7)}
    # gaps: [0,100) in no step span but the window, [250,300) and
    # [400,600), [900,1000); the longest first, named by the host span
    assert red["idle_gaps"][0] == ["bench.harvest", pytest.approx(2e-7)]
    names = {g[0] for g in red["idle_gaps"]}
    assert "host:other" in names and "bench.step" in names
    gemm = tr.op_seconds(red, red["module_calls"]["jit_decode_fn"],
                         lambda o: o[0] in GEMM_OPS)
    assert gemm == pytest.approx(1e-7)          # not the dot of the mixed call
    paged = tr.op_seconds(red, red["module_calls"]["jit_decode_fn"],
                          lambda o: o[0] == PAGED_ATTENTION)
    assert paged == pytest.approx(1e-7)


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]


def test_ops_outside_the_window_are_clipped():
    ex = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        op("xla:copy", -50, 100), op("xla:copy", 90, 50)]}},
        "spans": [["bench.traced", 0, 100]]}
    red = tr.reduce(ex)
    # [0, 50) of a and [90, 100) of b
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["idle_gaps"] == [["host:other", pytest.approx(40e-9)]]


def test_classify_real_v5e_op_texts():
    """One HLO op text of each kind, as a yi-6b decode step on a v5e
    printed them."""
    texts = json.loads((DATA / "v5e_op_texts.json").read_text())
    for kind, text in texts.items():
        assert tr.classify(text) == kind, text[:120]
    assert {"pallas:gemm", "pallas:paged_attention", "xla:param_copy",
            "xla:pool_copy"} <= set(texts)


def test_recorded_v5e_decode_trace():
    """Two calls of the yi-6b decode program (8 slots) recorded on one
    TPU v5e, reduced: module time, busy union, kernel time by kind and the
    idle gaps between the calls, named by the harness span."""
    with gzip.open(DATA / "v5e_yi6b_decode.json.gz", "rt") as f:
        ex = json.load(f)
    red = tr.reduce(ex)
    dec = red["modules"][DECODE]
    assert dec["calls"] == 2
    assert dec["seconds"] == pytest.approx(0.0843, abs=2e-4)   # 42.15 ms
    assert red["modules"]["jit__argmax"]["calls"] == 2
    assert 0.95 * dec["seconds"] < red["busy_s"] < red["window_s"]
    calls = red["module_calls"][DECODE]
    gemm = tr.op_seconds(red, calls, lambda o: o[0] in GEMM_OPS)
    paged = tr.op_seconds(red, calls, lambda o: o[0] == PAGED_ATTENTION)
    # weight slices 15.1 ms + GEMM kernels 7.1 ms per call; 32 paged
    # attention calls 13.6 ms per call
    assert gemm == pytest.approx(2 * 0.02225, rel=0.01)
    assert paged == pytest.approx(2 * 0.01363, rel=0.01)
    kinds = dict(red["top_ops"])
    assert kinds["pallas:gemm"] == pytest.approx(2 * 0.00714, rel=0.01)
    assert sum(kinds.values()) <= red["busy_s"] + 1e-9
    assert red["idle_gaps"] and all(g[0].startswith("bench.")
                                    for g in red["idle_gaps"])
    assert red["idle_gaps"][0][1] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=0.5)
