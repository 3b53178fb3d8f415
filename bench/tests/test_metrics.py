"""Every metric reader on a run record built around the recorded v5e
decode trace: each reads a number, and no share of a peak or a roofline
passes 100%."""

import gzip
import json
from pathlib import Path

import pytest

from bench import engine_trace as et, harness, trace as tr
from bench.models import llama

DATA = Path(__file__).resolve().parent / "data"
BENCH = Path(__file__).resolve().parents[1]


class _Req:
    def __init__(self, t_admit):
        self.t_admit = t_admit


class _Live:
    def __init__(self, due, stamps, t_admit):
        self.due, self.stamps, self.req = due, stamps, _Req(t_admit)


@pytest.fixture(scope="module")
def run():
    with gzip.open(DATA / "v5e_yi6b_decode.json.gz", "rt") as f:
        red = tr.reduce(json.load(f))
    c = json.loads((BENCH / "configs" / "yi-6b.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())
    # the two traced calls: 8 slots decoding at positions 300..307
    calls = [{"program": "decode", "t": 0.0, "prefill": [],
              "decode": list(range(300, 308)), "logits": 8, "live": 8}] * 2
    red["calls"] = calls
    # the engine's spans come from the trace recorded with them
    with gzip.open(DATA / "v5e_yi6b_decode_spans.json.gz", "rt") as f:
        red["engine"] = et.exposed(json.load(f))
    # eight requests due at 1 s, admitted 0.1 s apart, a token every 47 ms
    reqs = [_Live(1.0, [1.5 + 0.047 * k for k in range(200)], 1.0 + 0.1 * i)
            for i in range(8)]
    return {"window": (1.0, 9.0), "setup_s": 42.0, "requests": reqs,
            "calls": calls, "occupancy_slots": 8, "trace": red,
            "stats": [{"steps": 10, "live_rows": 80},
                      {"steps": 30, "live_rows": 240}],
            "family": llama, "config": c,
            "peaks": peaks["devices"]["TPU v5 lite"]}


def test_every_reader_reads(run):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if m["name"].startswith(("mixed_step", "mfu.mixed")):
            continue                       # no mixed call in this trace
        value = harness.load_reader(m["name"])(run)
        assert value is not None and value >= 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)
        assert cells or m["name"] == "setup_s"


def test_decode_readings(run):
    read = harness.load_reader
    assert read("decode_step_ms")(run) == pytest.approx(42.15, abs=0.05)
    # 160 tokens of each request between 1.5 s and 9 s, over 8 s
    assert read("output_tok_s")(run) == pytest.approx(8 * 160 / 8.0)
    assert read("itl_p95_ms")(run) == pytest.approx(47.0, abs=0.5)
    assert read("slot_occupancy")(run) == 100.0
    assert read("slot_occupancy.engine")(run) == 100.0
    assert 3.0 < read("host_exposed_ms.decode")(run) < 3.6
    assert 0 < read("prepare_exposed_ms.decode")(run) < 1.5
    assert 1.5 < read("sample_exposed_ms.decode")(run) < 3.0
    # 14.2 ms of least projection time over 22.25 ms of GEMM kernels and
    # the weight slices that feed them
    assert read("gemm_roofline.decode")(run) == pytest.approx(63.9, abs=1.0)
    assert read("paged_attn_roofline")(run) < 5.0
    assert 0 < read("idle_share.decode")(run) < 10.0
    assert 0 < read("mfu.decode")(run) < 2.0
    assert read("queue_wait_ms")(run) == pytest.approx(350.0)
    assert read("ttft_p50_ms")(run) == pytest.approx(500.0)
