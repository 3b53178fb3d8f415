"""Jit-ready wrappers around the Pallas kernels.

These are the public entry points the model code uses.  They

* pick elastic tiles per shape (:func:`repro.core.elastic.choose_tiles`),
* pad operands to tile multiples and slice the result back,
* fall back to the pure-jnp reference on non-TPU backends unless
  ``interpret=True`` is forced (Pallas TPU kernels do not lower on CPU; the
  test-suite validates the kernels in interpret mode, and the dry-run uses
  the reference path, whose HLO cost model is what the roofline reads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import elastic
from repro.kernels import ref
from repro.kernels.kraken_gemm import ACTIVATIONS, kraken_gemm
from repro.kernels.swa_attention import swa_attention as _swa_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, mult: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, -d % m) for d, m in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def kraken_matmul(a: jnp.ndarray, b: jnp.ndarray, *,
                  bias: jnp.ndarray | None = None,
                  activation: str | None = None,
                  out_dtype=None,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None,
                  tile_mode: str | None = None) -> jnp.ndarray:
    """Uniform-dataflow matmul: [M, K] @ [K, N] (+bias, +activation).

    The single compute primitive of the framework — conv, FC, attention
    projections and MoE experts all route through here (DESIGN.md §2).
    It is differentiable: the backward pass is two more GEMMs through the
    same kernel (``dA = dZ @ B^T``, ``dB = A^T @ dZ``).

    ``tile_mode`` selects the tile plan source (``"model"`` | ``"cached"`` |
    ``"autotune"``; ``None`` defers to the process-wide ``repro.tuning``
    policy) — a server started with ``--tile-cache`` replays empirically
    measured winners here instead of the static model's picks.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return ref.matmul(a, b, bias=bias, activation=activation,
                          out_dtype=out_dtype)
    return _matmul_vjp(a, b, bias, activation, out_dtype or a.dtype,
                       bool(interpret), tile_mode)


def _gemm(a, b, bias, activation, out_dtype, interpret, tile_mode):
    """One padded ``kraken_gemm`` call under the elastic tile plan."""
    m, k = a.shape
    _, n = b.shape
    cfg = elastic.choose_tiles(m, k, n, in_bytes=a.dtype.itemsize,
                               mode=tile_mode, dtype_name=a.dtype.name)
    ap = _pad_to(a, (cfg.bm, cfg.bk))
    bp = _pad_to(b, (cfg.bk, cfg.bn))
    bias_p = None
    if bias is not None:
        bias_p = _pad_to(bias.reshape(1, -1), (1, cfg.bn))
    out = kraken_gemm(
        ap, bp, bm=cfg.bm, bk=ap.shape[1] if cfg.schedule == "weight_stationary" else cfg.bk,
        bn=cfg.bn, schedule=cfg.schedule, bias=bias_p, activation=activation,
        out_dtype=out_dtype, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _matmul_vjp(a, b, bias, activation, out_dtype, interpret, tile_mode):
    return _gemm(a, b, bias, activation, out_dtype, interpret, tile_mode)


def _matmul_fwd(a, b, bias, activation, out_dtype, interpret, tile_mode):
    out = _gemm(a, b, bias, activation, out_dtype, interpret, tile_mode)
    return out, (a, b, bias)


def _matmul_bwd(activation, out_dtype, interpret, tile_mode, res, g):
    a, b, bias = res
    if activation is None:
        dz = g
    else:
        # the epilogue's derivative needs the pre-activation: recompute it
        # (one more GEMM) rather than keep an f32 [M, N] residual alive
        z = _gemm(a, b, bias, None, jnp.float32, interpret, tile_mode)
        _, act_vjp = jax.vjp(ACTIVATIONS[activation], z)
        dz = act_vjp(g.astype(jnp.float32))[0]
    dz = dz.astype(a.dtype)
    da = _gemm(dz, b.T, None, None, a.dtype, interpret, tile_mode)
    db = _gemm(a.T, dz, None, None, b.dtype, interpret, tile_mode)
    dbias = None if bias is None else \
        jnp.sum(dz.astype(jnp.float32), axis=0).astype(bias.dtype)
    return da, db, dbias


_matmul_vjp.defvjp(_matmul_fwd, _matmul_bwd)


def kraken_conv2d(x: jnp.ndarray, k: jnp.ndarray, *,
                  stride: tuple[int, int] = (1, 1),
                  padding: tuple[tuple[int, int], tuple[int, int]] = ((0, 0), (0, 0)),
                  out_dtype=None,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None,
                  tile_mode: str | None = None) -> jnp.ndarray:
    """Convolution by the uniform lowering conv -> im2col -> kraken_matmul.

    x: [N, H, W, C_i], k: [K_H, K_W, C_i, C_o].  This is the paper's
    uniformity insight applied TPU-natively: the conv becomes a GEMM cell
    instead of the GEMM becoming a degenerate conv.
    """
    n, h, w, c_i = x.shape
    k_h, k_w, _, c_o = k.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, (k_h, k_w), stride, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # patches: [N, OH, OW, C_i*K_H*K_W] with channel-major patch order.
    oh, ow = patches.shape[1], patches.shape[2]
    lhs = patches.reshape(n * oh * ow, c_i * k_h * k_w)
    # Match the patch ordering: (C_i, K_H, K_W) -> rows of the weight matrix.
    rhs = jnp.transpose(k, (2, 0, 1, 3)).reshape(c_i * k_h * k_w, c_o)
    out = kraken_matmul(lhs, rhs, out_dtype=out_dtype,
                        use_pallas=use_pallas, interpret=interpret,
                        tile_mode=tile_mode)
    return out.reshape(n, oh, ow, c_o)


def swa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  window: int, use_pallas: bool | None = None,
                  interpret: bool | None = None,
                  block_q: int = 128, block_kv: int = 128) -> jnp.ndarray:
    """Sliding-window flash attention; q,k,v: [B, H(q/kv), S, D]."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        # GQA: broadcast kv heads.
        if k.shape[1] != q.shape[1]:
            rep = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return ref.sliding_window_attention(q, k, v, window=window)
    return _swa_pallas(q, k, v, window=window, interpret=bool(interpret),
                       block_q=block_q, block_kv=block_kv)


def kraken_decode_attention(q, k, v, *, kv_pos, q_pos,
                            k_scale=None, v_scale=None, window: int = 0,
                            block_s: int = 512,
                            use_pallas: bool | None = None,
                            interpret: bool | None = None):
    """One-token GQA attention over a (possibly int8) KV cache.

    The serving-side uniform-dataflow kernel: int8 K/V are dequantized in
    VMEM (fused into the flash-decode loop), so the HBM read is half-width
    — the paper's Sec. II-D quantization applied to the decode memory
    floor (§Perf cell 3).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return ref.decode_attention(q, k, v, kv_pos=kv_pos, q_pos=q_pos,
                                    k_scale=k_scale, v_scale=v_scale,
                                    window=window)
    from repro.kernels.decode_attention import decode_attention as _dec
    return _dec(q, k, v, kv_pos=kv_pos, q_pos=q_pos, k_scale=k_scale,
                v_scale=v_scale, window=window, block_s=block_s,
                interpret=bool(interpret))


def kraken_paged_attention(q, k_pages, v_pages, *, pos_pages, page_table,
                           q_pos, k_scale=None, v_scale=None,
                           window: int = 0,
                           pages_per_block: int | None = None,
                           use_pallas: bool | None = None,
                           interpret: bool | None = None):
    """One-token GQA attention straight off a (possibly int8) page pool.

    The fused serving kernel (kernels/paged_attention.py): the page-table
    walk happens *inside* the grid via scalar-prefetched table/position
    operands, so per-token HBM traffic is the slot's live pages once — not
    the dense re-materialization of the whole cache the old decode path
    paid twice over.  ``pages_per_block`` defaults through the process-wide
    tile policy (``op_kind="paged_decode"`` cache entries, keyed
    ``m/k/n`` <- slots/logical_len/head_dim).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return ref.paged_decode_attention(
            q, k_pages, v_pages, pos_pages=pos_pages, page_table=page_table,
            q_pos=q_pos, k_scale=k_scale, v_scale=v_scale, window=window)
    from repro.kernels import paged_attention as pa
    if pages_per_block is None:
        mp = page_table.shape[1]
        ps = k_pages.shape[2]
        pages_per_block = pa.resolve_pages_per_block(
            slots=q.shape[0], logical_len=mp * ps, head_dim=q.shape[-1],
            page_size=ps, max_pages=mp, dtype_name=k_pages.dtype.name,
            kv_heads=k_pages.shape[1], q_heads=q.shape[1], window=window)
    return pa.paged_decode_attention(
        q, k_pages, v_pages, pos_pages=pos_pages, page_table=page_table,
        q_pos=q_pos, k_scale=k_scale, v_scale=v_scale, window=window,
        pages_per_block=pages_per_block, interpret=bool(interpret))
