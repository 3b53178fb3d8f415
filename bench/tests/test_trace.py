"""The trace reduction: which events it keeps, kernels by name, busy
union, device time per module, kernel matching inside a module's calls,
and idle gaps named by host spans; on hand-made events and on small traces
recorded on a TPU v5e."""

import gzip
import json
import re
from pathlib import Path
from types import SimpleNamespace

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


def operand_rule(text):
    """How ``classify`` told the two main-path kernels apart before they
    had names, kept as the oracle for the name rule: paged attention takes
    its scalar-prefetch operands (``s32`` page table and positions) first,
    a GEMM ``[M, K] @ [K, N]`` two matrices."""
    args = re.search(r"custom-call\((.*?)\), custom_call_target", text)
    ops = [(t, [int(x) for x in d.split(",") if x]) for t, d in
           re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", args.group(1))]
    if ops and ops[0][0].startswith(("s", "u")):
        return "pallas:paged_attention"
    if (len(ops) >= 2 and len(ops[0][1]) == 2 and len(ops[1][1]) == 2
            and ops[0][1][1] == ops[1][1][0]):
        return "pallas:gemm"
    return "pallas:other"


def test_classify_real_v5e_op_texts():
    """One HLO op text of each kind, as yi-6b's programs on a v5e printed
    them (``bench/engine_trace.py --op-texts``); the kernels carry their
    names, and the name rule gives the kind the operand rule gave."""
    texts = json.loads((DATA / "v5e_op_texts.json").read_text())
    for kind, text in texts.items():
        assert tr.classify(text) == kind, text[:120]
        if kind.startswith("pallas:"):
            assert operand_rule(text) == kind, text[:120]
    assert {"pallas:gemm", "pallas:paged_attention", "xla:param_copy",
            "xla:pool_copy"} <= set(texts)
    assert texts["pallas:gemm"].startswith("%kraken_gemm")
    assert texts["pallas:paged_attention"].startswith(
        "%paged_decode_attention")


def test_a_scalar_prefetch_kernel_is_known_by_its_name():
    """A grouped expert GEMM takes scalar-prefetch operands first, as paged
    attention does; its name alone decides its kind."""
    text = ('%grouped_moe_gemm.3 = bf16[2048,1024]{1,0:T(8,128)(2,1)} '
            'custom-call(s32[16]{0:T(128)} %starts, s32[16]{0:T(128)} '
            '%sizes, bf16[2048,2048]{1,0:T(8,128)(2,1)} %x, '
            'bf16[16,2048,1024]{2,1,0:T(8,128)(2,1)} %w), '
            'custom_call_target="tpu_custom_call"')
    assert operand_rule(text) == "pallas:paged_attention"
    assert tr.classify(text) == "pallas:grouped_moe_gemm"
    # a kernel that has no name of its own takes its enclosing function's
    assert tr.classify(text.replace("grouped_moe_gemm.3", "decode_fn.17")) \
        == "pallas:decode_fn"
    assert tr.classify(text.replace("tpu_custom_call", "Sharding")) \
        == "xla:custom-call:Sharding"


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=line, events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events]) for line, events in lines.items()])


def test_extract_keeps_device_events_and_the_bench_and_engine_spans():
    gemm = ('%kraken_gemm.4 = bf16[8,512]{1,0} custom-call(bf16[8,4096]{1,0}'
            ' %a, bf16[4096,512]{1,0} %b), custom_call_target='
            '"tpu_custom_call"')
    planes = [
        _plane("/device:TPU:0", {
            "XLA Modules": [("jit_decode_fn(1)", 100, 50)],
            "XLA Ops": [(gemm, 100, 20), ("%copy.3 = f32[8]{0} copy("
                                          "f32[8]{0} %pools__k.1)", 120, 5)],
            "Async XLA Ops": [("%copy-start.2 = (f32[8]) copy-start()",
                               125, 10)],
            "Steps": [("1", 100, 50)]}),
        _plane("/device:TPU:0 CUSTOM", {"XLA Ops": [(gemm, 0, 1)]}),
        _plane("/host:CPU", {
            "python": [("bench.traced", 0, 300), ("engine.step", 90, 70),
                       ("engine.sample", 150, 9), ("PjitFunction", 95, 3),
                       ("bench.step", 89, 72), ("TfrtCpuExecutable", 1, 2),
                       ("engine.admit", 91, 2)]}),
        _plane("/host:metadata", {"x": [("engine.step", 0, 1)]}),
    ]
    ex = tr.from_planes(planes)
    assert ex["devices"] == {"/device:TPU:0": {
        "modules": [["jit_decode_fn(1)", 100, 50]],
        "ops": [["pallas:gemm", 100, 20], ["xla:pool_copy", 120, 5],
                ["async:copy-start", 125, 10]]}}
    assert ex["spans"] == [
        ["bench.traced", 0, 300], ["bench.step", 89, 72],
        ["engine.step", 90, 70], ["engine.admit", 91, 2],
        ["engine.sample", 150, 9]]


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


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    """Three decode ticks recorded with the engine's spans: each long gap
    falls in the readback of the sampled ids or in the next tick's
    uploads, not in the harness's ``bench.step`` around them."""
    with gzip.open(DATA / "v5e_yi6b_decode_spans.json.gz", "rt") as f:
        red = tr.reduce(json.load(f))
    names = [g[0] for g in red["idle_gaps"]]
    assert all(n.startswith("engine.") for n in names), names
    assert names[:3] == ["engine.sample"] * 3
    assert "engine.inputs" in names
