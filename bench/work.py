"""Operations and bytes of the engine's programs, from configuration
shapes and the real tokens of each call.

Only useful work counts: the real tokens of a call (padded chunk columns
and idle slots count nothing), attention over each token's real context,
and the output head only for the tokens whose logits pick a served token.
So a share of a peak computed from these counts cannot pass 100% unless
the device time leaves out part of the work.
"""

from __future__ import annotations


def layer_flops(fam, c) -> float:
    """Projection FLOPs of one token through one layer."""
    return 2.0 * sum(k * n for k, n in fam.layer_matmuls(c))


def head_flops(fam, c) -> float:
    k, n = fam.head_matmul(c)
    return 2.0 * k * n


def token_flops(fam, c, pos: int) -> float:
    """One token at position ``pos`` (0-based) through every layer, head
    excluded."""
    n_layers = fam.dims(c)["n_layers"]
    return n_layers * (layer_flops(fam, c) + fam.mixer_flops(c, pos + 1))


def call_flops(fam, c, call: dict) -> float:
    """Useful FLOPs of one program call as the harness recorded it:
    ``prefill`` rows ``(start, n)``, ``decode`` positions, and ``logits``
    tokens served."""
    total = 0.0
    for start, n in call["prefill"]:
        total += sum(token_flops(fam, c, p) for p in range(start, start + n))
    total += sum(token_flops(fam, c, p) for p in call["decode"])
    return total + call["logits"] * head_flops(fam, c)


def gemm_least_seconds(fam, c, m: int, peaks: dict,
                       itemsize: int = 2) -> float:
    """Least time of one step's projections at ``m`` rows: each GEMM
    ``[m, K] @ [K, N]`` takes the larger of its FLOPs over the peak rate
    and its bytes (weights, input and output once) over the memory
    bandwidth; summed over every layer and the head."""
    n_layers = fam.dims(c)["n_layers"]

    def one(k, n):
        flops = 2.0 * m * k * n
        nbytes = itemsize * (k * n + m * k + m * n)
        return max(flops / peaks["bf16_flops_s"],
                   nbytes / peaks["hbm_bytes_s"])

    return (n_layers * sum(one(k, n) for k, n in fam.layer_matmuls(c))
            + one(*fam.head_matmul(c)))


def kv_read_bytes(fam, c, positions) -> float:
    """K/V bytes a decode call must read: every cached token of each live
    slot, the new one included, over all layers."""
    per = fam.kv_bytes_per_token(c)
    return float(sum((p + 1) * per for p in positions))
