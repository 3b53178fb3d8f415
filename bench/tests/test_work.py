"""FLOP and byte counts against hand counts for one yi-6b and one rwkv6-3b
step."""

import json
from pathlib import Path

import pytest

from bench import work
from bench.models import llama, rwkv6

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
V5E = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_yi6b_decode_step():
    c = load("yi-6b")
    # per layer, per token: q 4096x4096, k and v 4096x512, o 4096x4096,
    # gate and up 4096x11008, down 11008x4096 = 173,015,040 MACs
    assert work.layer_flops(llama, c) == 2 * 173_015_040
    # attention: 4 * 32 heads * 128 * ctx per layer
    assert llama.mixer_flops(c, 1000) == 4 * 32 * 128 * 1000
    # 8 slots decoding at position 999 (1000 keys), 8 tokens served
    call = {"prefill": [], "decode": [999] * 8, "logits": 8}
    per_token = 32 * (2 * 173_015_040 + 16_384 * 1000)
    head = 2 * 4096 * 64000
    assert work.call_flops(llama, c, call) == 8 * per_token + 8 * head
    assert work.call_flops(llama, c, call) == 96_972_308_480
    # projections at M = 8: weight bytes bound them; 11,597,250,560 bytes
    # of weights + 37,396,480 of activations at 819 GB/s
    assert work.gemm_least_seconds(llama, c, 8, V5E) == pytest.approx(
        11_634_647_040 / 819e9)
    # K and V: 2 x 4 heads x 128 x 2 bytes x 32 layers per cached token
    assert work.kv_read_bytes(llama, c, [999, 99]) == 65_536 * (1000 + 100)


def test_rwkv6_3b_prefill_chunk():
    c = load("rwkv6-3b")
    # r k v g o 5 x 2560^2, decay LoRA 2 x 2560 x 160, channel mix
    # 2 x 2560 x 8960 + 2560^2 = 86,016,000 MACs per layer
    assert work.layer_flops(rwkv6, c) == 2 * 86_016_000
    assert rwkv6.mixer_flops(c, 5) == 7 * 40 * 64 * 64
    # a first chunk of 256 prompt tokens that ends the prompt: one logit
    call = {"prefill": [(0, 256)], "decode": [], "logits": 1}
    per_token = 32 * (2 * 86_016_000 + 7 * 40 * 64 * 64)
    assert work.call_flops(rwkv6, c, call) == \
        256 * per_token + 2 * 2560 * 65536
    assert work.call_flops(rwkv6, c, call) == 1_419_016_929_280
    assert work.kv_read_bytes(rwkv6, c, [10, 20]) == 0


def test_parameter_counts_match_the_published_sizes():
    def params(fam, c):
        shapes = list(fam.layer_shapes(c).values())
        n = sum(int(__import__("math").prod(s)) for s in shapes)
        g = sum(int(__import__("math").prod(s))
                for s in fam.global_shapes(c).values())
        return fam.dims(c)["n_layers"] * n + g
    assert params(llama, load("yi-6b")) == 6_061_035_520
    assert 3.0e9 < params(rwkv6, load("rwkv6-3b")) < 3.2e9
