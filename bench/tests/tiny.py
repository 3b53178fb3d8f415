"""Small copies of the cells for CPU tests: the same families, traffic
shapes and harness, at widths a test run holds."""

import copy
import json
from pathlib import Path

from bench import traffic

BENCH = Path(__file__).resolve().parents[1]


def config(name: str, dtype: str = "bfloat16") -> dict:
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
             vocab_size=512, torch_dtype=dtype)
    if c["reference"] == "llama":
        c.update(num_attention_heads=4, num_key_value_heads=2)
        c["program"]["set"]["head_dim"] = 64
    else:
        c["program"]["set"]["ssm_heads"] = 4
    c["engine"].update(slots=4, chunk=32, max_len=256, page_size=16)
    c["correct"]["limits"] = {"mean_logit_gap": 0.01}
    return c


def mix(name: str) -> dict:
    m = copy.deepcopy(traffic.load_mix(name))
    m["prompt_tokens"].update(median=40, min=8, max=100)
    m["output_tokens"].update(median=12, min=4, max=40)
    m["warmup_s"] = 1.0
    if m["loop"] == "closed":
        m["clients"] = 4
    return m
