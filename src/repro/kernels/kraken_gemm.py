"""The uniform-dataflow GEMM: Kraken's engine as a Pallas TPU kernel.

Every compute op in the framework (FC layers, attention projections, MoE
experts, im2col'd convolutions, logits) lowers to this one kernel family —
the TPU realization of the paper's single uniform dataflow (DESIGN.md §2).

Two schedules, selected per layer by :func:`repro.core.elastic.choose_tiles`:

* ``weight_stationary`` — the full-K weight tile ``[K, bn]`` is VMEM-resident
  while the grid sweeps M tiles (its BlockSpec index map is independent of
  the fastest grid dimension, so Pallas never re-fetches it).  This is the
  weights-rotator: weights loaded once per "iteration" and rotated over all
  input positions, double-buffered by the Pallas pipeline exactly like the
  ping-pong W-SRAM / R-SRAM pair.
* ``output_stationary`` — K is split across the fastest grid dimension and
  partial sums live in an fp32 VMEM scratch accumulator until complete, the
  bare-bones-PE accumulation: partials never touch HBM.

The epilogue (bias + activation) rides the final k-step, the analogue of the
output pipe draining full sums without stalling the engine.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ACTIVATIONS = {
    None: lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": jax.nn.gelu,
}


def _epilogue(acc, bias_ref, activation):
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    return ACTIVATIONS[activation](acc)


def _ws_kernel(a_ref, b_ref, *rest, activation: Optional[str], has_bias: bool):
    """Weight-stationary: one full-K dot per output tile."""
    bias_ref, o_ref = (rest[0], rest[1]) if has_bias else (None, rest[0])
    acc = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = _epilogue(acc, bias_ref, activation).astype(o_ref.dtype)


def _os_kernel(a_ref, b_ref, *rest, nk: int, activation: Optional[str],
               has_bias: bool):
    """Output-stationary: accumulate over k grid steps in VMEM scratch."""
    if has_bias:
        bias_ref, o_ref, acc_ref = rest
    else:
        bias_ref, (o_ref, acc_ref) = None, rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], bias_ref, activation).astype(o_ref.dtype)


def kraken_gemm(a: jnp.ndarray, b: jnp.ndarray, *,
                bm: int, bk: int, bn: int, schedule: str,
                bias: jnp.ndarray | None = None,
                activation: str | None = None,
                out_dtype=None,
                interpret: bool = False) -> jnp.ndarray:
    """Tiled GEMM ``a @ b`` with fused epilogue.

    ``a``: [M, K], ``b``: [K, N]; M % bm == K % bk == N % bn == 0 (the ops.py
    wrapper pads).  ``bias``: [1, N] or None.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bk, bn)
    out_dtype = out_dtype or a.dtype
    out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    has_bias = bias is not None
    nm, nn, nk = m // bm, n // bn, k // bk

    if schedule == "weight_stationary":
        assert bk == k, "weight_stationary requires the full-K block"
        grid = (nn, nm)  # m fastest: the b tile (dep. on n only) stays put
        in_specs = [
            pl.BlockSpec((bm, k), lambda i_n, i_m: (i_m, 0)),
            pl.BlockSpec((k, bn), lambda i_n, i_m: (0, i_n)),
        ]
        if has_bias:
            in_specs.append(pl.BlockSpec((1, bn), lambda i_n, i_m: (0, i_n)))
        kernel = functools.partial(_ws_kernel, activation=activation,
                                   has_bias=has_bias)
        out_spec = pl.BlockSpec((bm, bn), lambda i_n, i_m: (i_m, i_n))
        scratch = []
    elif schedule == "output_stationary":
        grid = (nn, nm, nk)  # k fastest: partials accumulate in scratch
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i_n, i_m, i_k: (i_m, i_k)),
            pl.BlockSpec((bk, bn), lambda i_n, i_m, i_k: (i_k, i_n)),
        ]
        if has_bias:
            in_specs.append(pl.BlockSpec((1, bn), lambda i_n, i_m, i_k: (0, i_n)))
        kernel = functools.partial(_os_kernel, nk=nk, activation=activation,
                                   has_bias=has_bias)
        out_spec = pl.BlockSpec((bm, bn), lambda i_n, i_m, i_k: (i_m, i_n))
    else:
        raise ValueError(schedule)

    operands = (a, b) + ((bias,) if has_bias else ())
    if schedule == "weight_stationary":
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=out_shape, interpret=interpret, name="kraken_gemm",
        )(*operands)
    import jax.experimental.pallas.tpu as pltpu  # noqa: deferred import
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=out_shape, interpret=interpret, name="kraken_gemm",
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(*operands)
