"""Fused paged-attention decode: flash-decode straight off the page pools.

The serving engine's old decode path re-materialized the *entire* paged KV
cache into a dense ``[B, KV, L, D]`` tensor — gather, transpose, reshape —
for every generated token, so per-token HBM traffic was O(full cache) twice
over (read the pool, write the dense copy) before attention even ran.  This
kernel moves the page-table walk *inside* the grid: the table and the
per-slot query positions ride as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), and every grid step's BlockSpec index
map resolves the physical page to DMA from the table directly.  The pool is
read once, page by page, only for the pages a slot actually owns — the
Kraken lesson (memory traffic decided by the dataflow, not the instruction
mix) applied to the decode hot loop.

Layout per grid step ``(slot, page_block)``:

  q        [1, KV, G, D]    resident across page blocks (output-stationary)
  k/v      ppb x [1, KV, ps, D]  physical pages, every KV head, index-mapped
  pos      ppb x [1, 1, ps]  absolute position per entry (-2^30 = empty)
  k/v scale ppb x [1, KV, 1, ps] f32 (int8 pools only; folded into the dots)
  acc/m/l  VMEM scratch      online-softmax state, KV x G x D

The pool is ``[n_pages, KV, ps, D]``, so every KV head of one page is one
contiguous block: a step fetches it in one DMA, and the page's position
row once, for all heads.  The KV heads ride as the batch axis of the two
dots; the position row and its mask are built once per step and shared by
every head.  Positions and scales get a singleton axis so each block's last
two dims equal the array's (the TPU tiling rule for rows narrower than
(8, 128)), and every per-entry vector stays a lane row: the mask and the
scales are built by concatenating rows along lanes, never 1-D vectors.

``pages_per_block`` (ppb) logical pages are fetched per step — the tunable
the ``op_kind="paged_decode"`` autotuner measures.  Each page is its own
operand (same pool array, ppb index maps), because a slot's physical pages
are not contiguous: one BlockSpec cannot describe a multi-page gather.
The untuned ppb streams a 512-token stripe, lowered where the step's
double-buffered K and V blocks would outgrow ``KV_BLOCK_VMEM_BYTES``
(geometries with many KV heads).

Empty-block skip rule: a page is *dead* when its table entry is the
out-of-bounds sentinel (unallocated slot) or its first logical index lies
beyond ``q_pos`` (the ring has not wrapped far enough to reach it).  Dead
pages are index-mapped to physical page 0 — consecutive dead blocks then
present an unchanged block index, and the Pallas pipeline elides the
re-DMA — and the kernel forces every one of their position entries to the
empty sentinel (the fetched page-0 positions must never leak through).
The whole FLOP block is then skipped via ``pl.when`` whenever no entry
survives the position mask, which subsumes dead pages and additionally
skips blocks whose positions all fell out of the sliding window; a slot
with no surviving entry anywhere outputs exactly zero.  Ring wrap stays
exact because masking is position-based, same as the dense reference.

The dense gather survives only as the reference implementation
(``mode="reference"``, the off-TPU default and the oracle the property
tests pin this kernel to) — see ``models/layers._paged_decode``.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.elastic import ceil_div

POS_EMPTY = -(2 ** 30)  # matches models.layers.POS_EMPTY (no import: cycle)


# ---------------------------------------------------------------------------
# Decode-path policy: which implementation _paged_decode traces
# ---------------------------------------------------------------------------

PAGED_MODE_ENV = "KRAKEN_PAGED_DECODE"
_VALID_MODES = ("auto", "fused", "interpret", "reference")
_mode: str | None = None


def get_paged_decode_mode() -> str:
    """Process-wide paged-decode kernel mode: ``auto`` (TPU -> fused, else
    reference), ``fused`` (native Pallas), ``interpret`` (Pallas interpret —
    CI/property coverage of the real grid on CPU), ``reference`` (dense
    gather + XLA flash — the oracle)."""
    if _mode is not None:
        return _mode
    env = os.environ.get(PAGED_MODE_ENV, "auto")
    return env if env in _VALID_MODES else "auto"


def set_paged_decode_mode(mode: str | None) -> None:
    """Set (or with ``None``, reset to env/default) the process-wide mode."""
    global _mode
    if mode is not None and mode not in _VALID_MODES:
        raise ValueError(f"paged decode mode must be one of {_VALID_MODES}, "
                         f"got {mode!r}")
    _mode = mode


def resolve_paged_decode_mode() -> str:
    mode = get_paged_decode_mode()
    if mode == "auto":
        return "fused" if jax.default_backend() == "tpu" else "reference"
    return mode


@contextlib.contextmanager
def use_paged_decode_mode(mode: str | None):
    """Scope the decode-kernel mode over a trace (the engine jits its decode
    program under this, so two engines with different modes coexist).
    ``None`` is a no-op (defer to env/process default)."""
    if mode is None:
        yield
        return
    global _mode
    prev = _mode
    set_paged_decode_mode(mode)
    try:
        yield
    finally:
        _mode = prev


KV_BLOCK_VMEM_BYTES = 4 << 20  # a step's K + V blocks, double-buffered


def page_block_bytes(page_size: int, *, kv_heads: int, head_dim: int,
                     itemsize: int) -> int:
    """VMEM one logical page costs a grid step: its K and V blocks (every
    KV head), each double-buffered by the pipeline."""
    return 2 * 2 * kv_heads * page_size * head_dim * itemsize


def max_pages_per_block(page_size: int, *, kv_heads: int, head_dim: int,
                        itemsize: int) -> int:
    """The largest ppb whose K + V blocks fit ``KV_BLOCK_VMEM_BYTES``."""
    return max(1, KV_BLOCK_VMEM_BYTES // page_block_bytes(
        page_size, kv_heads=kv_heads, head_dim=head_dim, itemsize=itemsize))


def default_pages_per_block(page_size: int, max_pages: int, *, kv_heads: int,
                            head_dim: int, itemsize: int) -> int:
    """Untuned ppb: the same ~512-slot KV stripe per grid step that
    ``decode_attention``'s ``block_s`` default streams, bounded by the VMEM
    budget of a step that holds every KV head of each page."""
    return max(1, min(max_pages, 512 // max(1, page_size),
                      max_pages_per_block(page_size, kv_heads=kv_heads,
                                          head_dim=head_dim,
                                          itemsize=itemsize)))


def resolve_pages_per_block(*, slots: int, logical_len: int, head_dim: int,
                            page_size: int, max_pages: int, dtype_name: str,
                            kv_heads: int = 1, q_heads: int | None = None,
                            window: int = 0) -> int:
    """ppb under the process-wide tile policy (mirrors ``choose_tiles``):
    ``model`` -> static default; ``cached`` -> replay a persisted
    ``op_kind="paged_decode"`` winner (key ``m/k/n`` <-
    slots/logical_len/head_dim, entry validated against ``page_size`` and
    ``kv_heads``) or fall back; ``autotune`` -> measure the miss and
    persist it."""
    from repro import tuning
    from repro.tuning import cache as tcache
    from repro.tuning.search import lookup_paged_decode
    default = default_pages_per_block(
        page_size, max_pages, kv_heads=kv_heads, head_dim=head_dim,
        itemsize=jnp.dtype(dtype_name).itemsize)
    mode = tuning.get_tile_mode()
    if mode == "model":
        return default
    cache = tuning.get_tile_cache()
    key = tcache.cache_key("paged_decode", slots, logical_len, head_dim,
                           dtype_name, tuning.backend_name())
    hit = lookup_paged_decode(cache, key, page_size=page_size,
                              kv_heads=kv_heads, max_pages=max_pages)
    if hit is not None:
        return hit
    if mode == "autotune":
        from repro.tuning.search import autotune_paged_decode
        return autotune_paged_decode(
            slots, logical_len, head_dim, page_size=page_size,
            kv_heads=kv_heads, q_heads=q_heads, window=window,
            dtype_name=dtype_name, cache=cache)
    return default


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

def _kernel(tbl_ref, qpos_ref, q_ref, *refs, ppb: int, nblk: int,
            n_pages: int, page_size: int, window: int, scale: float,
            quantized: bool):
    n_in = (5 if quantized else 3) * ppb
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    pos_refs = refs[2 * ppb:3 * ppb]
    ksc_refs = refs[3 * ppb:4 * ppb] if quantized else ()
    vsc_refs = refs[4 * ppb:5 * ppb] if quantized else ()
    o_ref, m_ref, l_ref, acc_ref = refs[n_in:]

    b = pl.program_id(0)
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qpos_ref[b]
    poss = []
    for j in range(ppb):
        pid = tbl_ref[b, pb * ppb + j]
        # page liveness (module docstring): unallocated, or the ring has
        # not reached this page's first logical index yet.  A dead page was
        # index-mapped to physical page 0: whatever was fetched, every one
        # of its entries must read as empty.
        live = (pid < n_pages) & ((pb * ppb + j) * page_size <= q_pos)
        poss.append(jnp.where(live, pos_refs[j][0], POS_EMPTY))   # [1, ps]
    # every per-entry vector is a 2-D lane row: Mosaic concatenates
    # [1, ps] tiles along lanes, not 1-D vectors
    kv_pos = jnp.concatenate(poss, axis=1)                # [1, ppb*ps]

    # the block-skip predicate: does any entry survive the position mask?
    # Sentinel/unreached pages were forced to POS_EMPTY above, so this
    # subsumes the page-liveness test and additionally skips blocks whose
    # positions all fell out of the sliding window.  Everything beyond the
    # cheap position row — dequant, concat, both dots — stays inside the
    # skipped body.  Positions are per page, not per head: one mask serves
    # every KV head.
    mask = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window:
        mask = mask & (kv_pos > q_pos - window)

    @pl.when(jnp.any(mask))
    def _update():
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)  # [KV, S, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        q = q_ref[0]                                      # [KV, G, D]
        if quantized:
            # dequant folded into the dots: a per-entry scale multiplies
            # column s of q.k^T and row s of v, i.e. one lane row per head
            k_sc = jnp.concatenate([r[0] for r in ksc_refs], axis=2)
            v_sc = jnp.concatenate([r[0] for r in vsc_refs], axis=2)
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [KV, G, S]
        if quantized:
            logits = logits * k_sc                        # [KV, 1, S]
        logits = logits * scale
        masked = jnp.where(mask[None], logits, -1e30)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.max(masked, axis=-1, keepdims=True)   # [KV, G, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(masked - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * v_sc
        acc_ref[...] = acc_prev * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [KV, G, D]
        m_ref[...] = m_new

    @pl.when(pb == nblk - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, *, pos_pages: jnp.ndarray,
                           page_table: jnp.ndarray, q_pos: jnp.ndarray,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           window: int = 0,
                           pages_per_block: int | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """One-token GQA attention straight off a (possibly int8) page pool.

    q: [B, H, D]; k_pages/v_pages: [n_pages, KV, page_size, D] (int8 if
    scales given, scales [n_pages, KV, page_size] f32); pos_pages:
    [n_pages, page_size] absolute positions (-2^30 empty); page_table:
    [B, max_pages] physical page per (slot, logical page), out-of-bounds
    sentinel ``n_pages`` for unallocated rows; q_pos: [B] per-slot
    positions.  Returns [B, H, D]; slots with no live page return zeros.
    ``pages_per_block``: logical pages per grid step, every KV head of each.
    """
    b, h, d = q.shape
    n_pages, kvh, ps, _ = k_pages.shape
    mp = page_table.shape[1]
    g = h // kvh
    quantized = k_scale is not None
    ppb = pages_per_block or default_pages_per_block(
        ps, mp, kv_heads=kvh, head_dim=d, itemsize=k_pages.dtype.itemsize)
    ppb = max(1, min(int(ppb), mp))
    nblk = ceil_div(mp, ppb)
    tbl = jnp.asarray(page_table, jnp.int32)
    if nblk * ppb != mp:
        # sentinel-pad the table so every block holds ppb entries; the pad
        # pages are dead by construction (skip rule) and cost no traffic
        tbl = jnp.pad(tbl, [(0, 0), (0, nblk * ppb - mp)],
                      constant_values=n_pages)
    qpos_arr = jnp.broadcast_to(
        jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    # a singleton axis makes each per-page block's last two dims equal the
    # array's, the TPU tiling rule for rows narrower than (8, 128)
    pos_pages = jnp.asarray(pos_pages, jnp.int32).reshape(n_pages, 1, ps)

    def page_map(j, ndim):
        def m(bi, pb, tbl, qp):
            pid = tbl[bi, pb * ppb + j]
            live = (pid < n_pages) & ((pb * ppb + j) * ps <= qp[bi])
            # dead pages fetch physical page 0; consecutive dead blocks then
            # keep the block index unchanged and the pipeline skips the DMA
            idx = jnp.where(live, pid, 0)
            return (idx,) + (0,) * (ndim - 1)
        return m

    slot_map = lambda bi, pb, tbl, qp: (bi, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, kvh, g, d), slot_map)]
    in_specs += [pl.BlockSpec((1, kvh, ps, d), page_map(j, 4))
                 for j in range(ppb)]
    in_specs += [pl.BlockSpec((1, kvh, ps, d), page_map(j, 4))
                 for j in range(ppb)]
    in_specs += [pl.BlockSpec((1, 1, ps), page_map(j, 3))
                 for j in range(ppb)]
    args = ([q.reshape(b, kvh, g, d)] + [k_pages] * ppb + [v_pages] * ppb
            + [pos_pages] * ppb)
    if quantized:
        sc_spec = [pl.BlockSpec((1, kvh, 1, ps), page_map(j, 4))
                   for j in range(ppb)]
        in_specs += sc_spec + sc_spec
        args += ([k_scale.reshape(n_pages, kvh, 1, ps)] * ppb
                 + [v_scale.reshape(n_pages, kvh, 1, ps)] * ppb)

    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, d), slot_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, nblk=nblk, n_pages=n_pages,
                          page_size=ps, window=window,
                          scale=1.0 / (d ** 0.5), quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret, name="paged_decode_attention",
    )(tbl, qpos_arr, *args)
    return out.reshape(b, h, d)
