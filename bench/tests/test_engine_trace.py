"""The engine's host spans beside the device trace (``bench/engine_trace.py``):
program-free time split over the innermost span, the decode tick's
exposed host time, its preparation and its sampling, and the readers that
take them and the engine's counters; on hand-made events, on a TPU v5e
trace recorded with the harness's spans only, and on one recorded with the
engine's spans and the kernels' names."""

import gzip
import json
from pathlib import Path

import pytest

from bench import engine_trace as et
from bench import harness, trace as tr
from bench.models import llama

DATA = Path(__file__).resolve().parent / "data"
BENCH = Path(__file__).resolve().parents[1]
OLD = DATA / "v5e_yi6b_decode.json.gz"            # harness spans only
NEW = DATA / "v5e_yi6b_decode_spans.json.gz"      # with engine spans
TRACE_READERS = ("decode_step_ms", "mfu.decode", "gemm_roofline.decode",
                 "paged_attn_roofline", "idle_share.decode")


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def hand_made():
    """Two decode ticks in a 1000 ns window: program-free
    [0,100) [400,420) [440,600) [900,920) [940,1000)."""
    tick = [["engine.step", 50, 510], ["engine.schedule", 55, 15],
            ["engine.inputs", 70, 20], ["engine.dispatch.decode", 90, 20],
            ["engine.sample", 110, 390], ["engine.advance", 500, 40],
            ["engine.push_tables", 540, 15],
            ["engine.step", 585, 405], ["engine.schedule", 588, 7],
            ["engine.inputs", 595, 3], ["engine.dispatch.decode", 598, 7],
            ["engine.sample", 605, 355], ["engine.advance", 960, 20]]
    harness_spans = [["bench.traced", 0, 1000], ["bench.step", 40, 525],
                     ["bench.harvest", 566, 14]]
    modules = [["jit_decode_fn(1)", 100, 300], ["jit__argmax(2)", 420, 20],
               ["jit_decode_fn(1)", 600, 300], ["jit__argmax(2)", 920, 20]]
    return {"devices": {"/device:TPU:0": {
        "modules": modules,
        "ops": [["pallas:gemm", s, d] for _, s, d in modules]}},
        "spans": sorted(harness_spans + tick, key=lambda s: s[1])}


def test_program_free_time_goes_to_the_innermost_span():
    split = {k: v * 1e9 for k, v in et.host_idle(hand_made()).items()}
    # [440, 600) crosses sample, advance, push_tables, the end of one
    # tick, the harness between ticks and the start of the next
    assert split == pytest.approx({
        "host:other": 40 + 1 + 5 + 10, "bench.step": 10 + 5,
        "engine.step": 5 + 5 + 3 + 10, "engine.schedule": 15 + 7,
        "engine.inputs": 20 + 3, "engine.dispatch.decode": 10 + 2,
        "engine.sample": 20 + 60 + 20 + 20, "engine.advance": 40 + 20,
        "engine.push_tables": 15, "bench.harvest": 14})
    assert sum(split.values()) == pytest.approx(100 + 20 + 160 + 20 + 60)


def test_exposed_time_per_decode_tick():
    got = et.exposed(hand_made())
    assert got["decode_ticks"] == 2
    # tick 1: 50 + 20 + 120 ns; tick 2: 15 + 20 + 50 ns
    assert got["host_exposed_ms.decode"] == pytest.approx(
        (190 + 85) / 2 * 1e-6)
    # schedule + inputs + the dispatch's part before the program starts
    assert got["prepare_exposed_ms.decode"] == pytest.approx(
        (15 + 20 + 10 + 7 + 3 + 2) / 2 * 1e-6)
    # the argmax readback waits and the tail after it
    assert got["sample_exposed_ms.decode"] == pytest.approx(
        (20 + 60 + 20 + 20) / 2 * 1e-6)


def test_only_whole_decode_ticks_in_the_window_count():
    ex = hand_made()
    ex["spans"] = [s for s in ex["spans"] if s[1] < 585]        # tick 1
    ex["spans"] += [["engine.step", 585, 405],
                    ["engine.dispatch.mixed", 598, 7],           # not decode
                    ["engine.step", 995, 20],                    # past the end
                    ["engine.dispatch.decode", 996, 2]]
    ex["spans"].sort(key=lambda s: s[1])
    got = et.exposed(ex)
    assert got["decode_ticks"] == 1
    assert got["host_exposed_ms.decode"] == pytest.approx(190e-6)


def test_record_keeps_whole_ticks_and_summary_reads_them():
    rec = et.record(hand_made(), 1)
    assert tr.traced_window(rec) == (50, 560)
    assert [m[1] for m in rec["devices"]["/device:TPU:0"]["modules"]] \
        == [100, 420]
    assert ["bench.step", 40, 525] in rec["spans"]
    got = et.summary(rec)
    assert got["decode_ticks"] == 1
    assert got["host_exposed_ms.decode"] == pytest.approx(190e-6)
    assert got["decode_exposed_s"] == pytest.approx(190e-9)
    assert got["program_free_s"] == pytest.approx(190e-9)
    assert got["compile_spans"] == 0
    # the reduction names a gap by the span at its midpoint: [440, 560)
    assert got["idle_gaps"][0] == ["engine.advance", pytest.approx(120e-9)]


def test_a_trace_without_engine_spans_reads_none():
    ex = load(OLD)
    got = et.exposed(ex)
    assert got["decode_ticks"] == 0
    assert all(got[k] is None for k in got if k.endswith(".decode"))
    assert all(k.startswith("bench.") or k == et.NO_SPAN
               for k in et.host_idle(ex))


def reader_run(ex):
    """The run record ``bench/tests/test_metrics.py`` builds around a
    recorded trace: two decode calls of 8 slots."""
    red = tr.reduce(ex)
    calls = [{"program": "decode", "t": 0.0, "prefill": [],
              "decode": list(range(300, 308)), "logits": 8, "live": 8}] * 2
    red["calls"] = calls
    peaks = json.loads((BENCH / "peaks.json").read_text())
    return {"window": (1.0, 9.0), "setup_s": 42.0, "requests": [],
            "calls": calls, "occupancy_slots": 8, "trace": red,
            "family": llama,
            "config": json.loads((BENCH / "configs" / "yi-6b.json")
                                 .read_text()),
            "peaks": peaks["devices"]["TPU v5 lite"]}


def readings(ex):
    run = reader_run(ex)
    return {m: harness.load_reader(m)(run) for m in TRACE_READERS}


def test_readers_on_the_harness_only_trace_are_unchanged():
    """The trace readers' values on the trace recorded with the harness's
    spans only, exactly as the reduction gave them before the engine had
    spans."""
    assert readings(load(OLD)) == {
        "decode_step_ms": 42.148257,
        "mfu.decode": 1.1327571894874977,
        "gemm_roofline.decode": 63.84608036139373,
        "paged_attn_roofline": 1.4303151515935226,
        "idle_share.decode": 5.7042664243659225}


def test_engine_spans_leave_the_readers_bit_identical():
    """The same recorded trace with and without the engine's spans and
    the kernels' names: every trace reader reads the same bits; only the
    idle gaps' names change, to engine spans."""
    ex = load(NEW)
    bare = {"devices": {k: {"modules": d["modules"], "ops": d["ops"]}
                        for k, d in ex["devices"].items()},
            "spans": [s for s in ex["spans"] if s[0].startswith("bench.")]}
    assert readings(ex) == readings(bare)
    # as the readers gave them before the reduction kept engine spans
    assert readings(ex) == {
        "decode_step_ms": 42.229993666666665,
        "mfu.decode": 1.1305647241619703,
        "gemm_roofline.decode": 63.84066903104505,
        "paged_attn_roofline": 1.4218883234065742,
        "idle_share.decode": 7.026283920682619}
    assert harness.load_reader("slot_occupancy")(reader_run(ex)) == 100.0
    gaps = tr.reduce(ex)["idle_gaps"]
    assert [g[1] for g in gaps] == [g[1] for g in
                                    tr.reduce(bare)["idle_gaps"]]
    assert gaps[0][0].startswith("engine.")


def test_recorded_v5e_trace_with_engine_spans():
    """Decode ticks of yi-6b (8 slots) on one TPU v5e, with the engine's
    spans: the ticks' exposed host time covers most of the window's
    program-free time, and every part is inside it."""
    ex = load(NEW)
    got = et.exposed(ex)
    assert got["decode_ticks"] >= 2
    host = got["host_exposed_ms.decode"]
    assert 0 < got["prepare_exposed_ms.decode"] < host
    assert 0 < got["sample_exposed_ms.decode"] < host
    t0, t1 = tr.traced_window(ex)
    free = sum(e - s for s, e in et.program_free(ex, t0, t1)) * 1e-6
    assert host * got["decode_ticks"] <= free + 1e-9
    assert host * got["decode_ticks"] >= 0.9 * free


def test_kernel_names_agree_with_the_operand_rule():
    """Every Pallas op of the recorded trace carries its kernel's name
    beside the kind the operand rule recorded for it; the name, through
    ``bench.trace``'s one table, gives that kind."""
    kernels = [k for d in load(NEW)["devices"].values()
               for k in d["kernels"]]
    ops = {tuple(o) for d in load(NEW)["devices"].values() for o in d["ops"]}
    assert {name for name, *_ in kernels} == set(tr.KERNELS)
    for name, kind, start, dur in kernels:
        assert tr.kernel_kind(name) == kind, (name, kind)
        assert (kind, start, dur) in ops


EXPOSED = {"decode_ticks": 2, "host_exposed_ms.decode": 3.4,
           "prepare_exposed_ms.decode": 1.0, "sample_exposed_ms.decode": 2.3}
SPAN_READERS = ("host_exposed_ms.decode", "prepare_exposed_ms.decode",
                "sample_exposed_ms.decode")


def test_span_readers_read_the_engine_reduction():
    for name in SPAN_READERS:
        read = harness.load_reader(name)
        assert read({"trace": {"engine": EXPOSED}}) == EXPOSED[name]
        assert read({"trace": None}) is None
        assert read({"trace": {"modules": {}}}) is None
        # a window with no decode tick
        assert read({"trace": {"engine": et.exposed(load(OLD))}}) is None


def test_span_readers_on_the_recorded_trace():
    """The harness reduces a traced window so: ``engine`` holds
    :func:`exposed` over the same window as the rest of the reduction."""
    ex = load(NEW)
    run = reader_run(ex)
    run["trace"]["engine"] = et.exposed(ex, run["trace"]["t0_ns"],
                                        run["trace"]["t1_ns"])
    got = {m: harness.load_reader(m)(run) for m in SPAN_READERS}
    assert got == {m: et.exposed(ex)[m] for m in SPAN_READERS}
    assert 3.0 < got["host_exposed_ms.decode"] < 3.6


def test_slot_occupancy_engine_reads_the_counters_over_the_window():
    read = harness.load_reader("slot_occupancy.engine")
    start = {"steps": 40, "live_rows": 300, "ticks": 41}
    # 100 program calls on 8 slots: 790 decode rows and 5 prefill rows
    end = {"steps": 140, "live_rows": 1095, "ticks": 150}
    assert read({"stats": [start, end], "occupancy_slots": 8}) \
        == pytest.approx(100.0 * 795 / 800)
    assert read({"stats": [start, start], "occupancy_slots": 8}) is None
    assert read({"stats": None, "occupancy_slots": 8}) is None
    assert read({"occupancy_slots": 8}) is None
    assert read({"stats": [{"steps": 1}, {"steps": 2}],
                 "occupancy_slots": 8}) is None
