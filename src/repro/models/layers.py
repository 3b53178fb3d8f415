"""Model building blocks: norms, RoPE, dense (uniform-GEMM), attention, MLPs.

Every matmul routes through :func:`dense`, which on TPU dispatches to the
Pallas ``kraken_gemm`` uniform-dataflow kernel and elsewhere to an einsum
with identical semantics — the framework-wide single compute primitive
(DESIGN.md §2).  Key activations carry logical-axis sharding constraints.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.kernels import ops

Params = dict


class Spec(NamedTuple):
    """Parameter spec: shape + logical axes + init scale."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    scale: float = 1.0  # stddev multiplier on 1/sqrt(fan_in); 0 -> zeros, -1 -> ones


def init_param(key, spec: Spec, dtype) -> jax.Array:
    if spec.scale == 0.0:
        return jnp.zeros(spec.shape, dtype)
    if spec.scale == -1.0:
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.shape[0] if len(spec.shape) == 1 else spec.shape[-2]
    std = spec.scale / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, params: Params, prefix: str, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, params[f"{prefix}_gamma"], params[f"{prefix}_beta"], cfg.norm_eps)
    return rms_norm(x, params[f"{prefix}_gamma"], cfg.norm_eps)


def norm_specs(cfg, prefix: str) -> dict[str, Spec]:
    s = {f"{prefix}_gamma": Spec((cfg.d_model,), ("embed",), -1.0)}
    if cfg.norm == "layernorm":
        s[f"{prefix}_beta"] = Spec((cfg.d_model,), ("embed",), 0.0)
    return s


# ---------------------------------------------------------------------------
# Positional encodings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, D]; positions: [S] shared across the batch, or [B, S]
    per-slot (continuous batching: every sequence sits at its own absolute
    position)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, half]
    if positions.ndim == 2:  # [B, S, half] -> broadcast over the heads dim
        ang = ang[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions: jax.Array, d_model: int) -> jax.Array:
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# The uniform-GEMM dense layer
# ---------------------------------------------------------------------------

def _use_kernels() -> bool:
    """Pallas kernels run on a TPU outside a multi-device mesh: XLA cannot
    partition a Mosaic kernel, so under a mesh the XLA ops stay."""
    return jax.default_backend() == "tpu" and not sharding.partitioned()


def dense(x: jax.Array, w: jax.Array, *, bias: jax.Array | None = None,
          activation: str | None = None) -> jax.Array:
    """x: [..., K] @ w: [K, N].  Routes through the uniform dataflow."""
    if _use_kernels():
        lead = x.shape[:-1]
        out = ops.kraken_matmul(x.reshape(-1, x.shape[-1]), w, bias=bias,
                                activation=activation, use_pallas=True)
        return out.reshape(*lead, w.shape[-1])
    out = jnp.einsum("...k,kn->...n", x, w)
    if bias is not None:
        out = out + bias
    if activation == "silu":
        out = jax.nn.silu(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    elif activation == "relu":
        out = jax.nn.relu(out)
    elif activation is not None:
        raise ValueError(activation)
    return out


# ---------------------------------------------------------------------------
# Attention (GQA; full/sliding-window/cross; train + prefill + cached decode)
# ---------------------------------------------------------------------------

def attention_specs(cfg, prefix: str = "attn", kv_source_dim: int | None = None) -> dict[str, Spec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_src = kv_source_dim or d
    s = {
        f"{prefix}_wq": Spec((d, h * hd), ("embed", "qkv")),
        f"{prefix}_wk": Spec((kv_src, kv * hd), ("embed", "qkv")),
        f"{prefix}_wv": Spec((kv_src, kv * hd), ("embed", "qkv")),
        f"{prefix}_wo": Spec((h * hd, d), ("qkv", "embed")),
    }
    if cfg.qkv_bias:
        s[f"{prefix}_bq"] = Spec((h * hd,), ("qkv",), 0.0)
        s[f"{prefix}_bk"] = Spec((kv * hd,), ("qkv",), 0.0)
        s[f"{prefix}_bv"] = Spec((kv * hd,), ("qkv",), 0.0)
    return s


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)  # [B, H, S, D]


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _gqa_sdpa_direct(q, k, v, *, mask_mode: str, window: int, q_pos, kv_pos) -> jax.Array:
    """Reference attention: q [B,H,Sq,D], k/v [B,KV,Sk,D].

    Inputs stay in the compute dtype with f32 *accumulation*
    (``preferred_element_type``) — an earlier revision upcast k/v to f32
    before the einsums, which (a) on TPU forces the dots off the bf16 MXU
    path and (b) on the CPU dry-run host made float-normalization carry a
    full f32 twin of the stacked KV cache through the layer scan,
    fabricating ~100x the decode cell's real cache traffic.
    §Perf cell-3 iteration 1.
    """
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    qg = q.reshape(b, kvh, group, sq, d)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if mask_mode != "none":
        # Positions may be shared ([Sq]/[Sk]) or per-slot ([B, Sq]/[B, Sk],
        # continuous batching); normalize both to [B|1, Sq, Sk].
        qp = q_pos[None, :, None] if q_pos.ndim == 1 else q_pos[:, :, None]
        kp = kv_pos[None, None, :] if kv_pos.ndim == 1 else kv_pos[:, None, :]
        # kp >= 0 excludes empty cache slots (pos sentinel is -2^30).
        mask = (kp <= qp) & (kp >= 0)
        if window:
            mask = mask & (kp > qp - window)
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, sq, d).astype(q.dtype)


_CHUNK_Q = 1024
_CHUNK_KV = 1024


def _gqa_sdpa_chunked(q, k, v, *, window: int, q_pos, kv_pos,
                      causal: bool, return_state: bool = False,
                      allow_window_slice: bool = True):
    """Flash-style double-chunked attention in jnp (the XLA counterpart of
    the Pallas swa_attention kernel, used for long prefill/train sequences).

    Online-softmax over kv chunks inside a scan over q chunks keeps the live
    logits tile at [B, H, cq, ckv] instead of [B, H, S, S].  For
    sliding-window layers only the ``window + cq`` kv slice of each q chunk
    is even read (dynamic_slice), so compute is O(S*W) like the TPU kernel.

    ``return_state=True`` returns the *unnormalized* softmax state
    ``(acc [B,KV,G,S,D] f32, m, l [B,KV,G,S,1] f32)`` instead of the
    normalized output — the context-parallel wrapper combines states
    across kv shards.  ``allow_window_slice=False`` disables the global
    window dynamic-slice (indices are global; inside shard_map the kv is
    a local shard, so masking must do the windowing).
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    cq, ckv = min(_CHUNK_Q, sq), min(_CHUNK_KV, skv)
    pad_q = -sq % cq
    qp = jnp.pad(q_pos, (0, pad_q), constant_values=2 ** 30)
    qpad = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    nq = qpad.shape[2] // cq
    scale = 1.0 / math.sqrt(d)

    # kv padded to ckv multiples; padded slots masked via kv_pos sentinel.
    pad_kv = -skv % ckv
    kpad = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    kvp = jnp.pad(kv_pos, (0, pad_kv), constant_values=-(2 ** 30))
    skv_p = kpad.shape[2]

    use_window_slice = (allow_window_slice and bool(window)
                        and (window + cq) * 2 <= skv_p)
    if use_window_slice:
        wlen = ((window + cq + ckv - 1) // ckv) * ckv
    else:
        wlen = skv_p
    nkv = wlen // ckv

    qr = qpad.reshape(b, kvh, group, nq, cq, d).transpose(3, 0, 1, 2, 4, 5)
    qpos_c = qp.reshape(nq, cq)

    def q_chunk(_, qc):
        qi, qck, qpc = qc   # index, [B,KV,G,cq,D], [cq]
        if use_window_slice:
            start = jnp.clip(qi * cq + cq - wlen, 0, skv_p - wlen)
            kw = jax.lax.dynamic_slice_in_dim(kpad, start, wlen, axis=2)
            vw = jax.lax.dynamic_slice_in_dim(vpad, start, wlen, axis=2)
            kpw = jax.lax.dynamic_slice_in_dim(kvp, start, wlen, axis=0)
        else:
            kw, vw, kpw = kpad, vpad, kvp

        kr = kw.reshape(b, kvh, nkv, ckv, d).transpose(2, 0, 1, 3, 4)
        vr = vw.reshape(b, kvh, nkv, ckv, d).transpose(2, 0, 1, 3, 4)
        kpr = kpw.reshape(nkv, ckv)

        def kv_chunk(carry, kc):
            m, l, acc = carry
            kck, vck, kpc = kc
            # compute-dtype inputs, f32 accumulation (see _gqa_sdpa_direct)
            logits = jnp.einsum("bkgqd,bksd->bkgqs", qck, kck,
                                preferred_element_type=jnp.float32) * scale
            mask = kpc[None, :] >= 0
            if causal:
                mask = mask & (kpc[None, :] <= qpc[:, None])
            if window:
                mask = mask & (kpc[None, :] > qpc[:, None] - window)
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_cur = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bkgqs,bksd->bkgqd", p.astype(vck.dtype), vck,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, group, cq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, group, cq, 1), jnp.float32)
        a0 = jnp.zeros((b, kvh, group, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_chunk, (m0, l0, a0), (kr, vr, kpr))
        if return_state:
            return None, (acc, m, l)
        out = acc / jnp.where(l == 0.0, 1.0, l)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_chunk, None,
                           (jnp.arange(nq), qr, qpos_c))
    if return_state:
        accs, ms, ls = outs

        def _unchunk(t):  # [nq, B, KV, G, cq, X] -> [B, KV, G, S, X]
            t = t.transpose(1, 2, 3, 0, 4, 5)
            t = t.reshape(b, kvh, group, nq * cq, t.shape[-1])
            return t[:, :, :, :sq]
        return _unchunk(accs), _unchunk(ms), _unchunk(ls)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, nq * cq, d)
    return out[:, :, :sq]


def _gqa_sdpa_context_parallel(q, k, v, *, window: int, q_pos, kv_pos,
                               axis: str) -> jax.Array:
    """Context-parallel flash attention under shard_map.

    For heads that do not divide the model axis (llama4 / llama-3.2: 40 H,
    8 KV on a 16-way axis), GSPMD's only pjit-expressible plan replicates
    the whole attention computation — 16x redundant FLOPs and tile
    traffic (§Perf bonus cell).  Instead: shard the *kv sequence* over the
    model axis, run local flash partials, and combine the online-softmax
    states across shards (pmax/psum of [B,KV,G,S,1]-sized m/l and the
    [.., D] accumulator) — ring-attention's combine without the ring.
    """
    c = sharding.current()
    mesh = c["mesh"]
    P = jax.sharding.PartitionSpec
    batch_axes = c["rules"].get("batch") or None
    bspec = tuple(batch_axes) if batch_axes else None

    def body(ql, kl, vl, qpl, kpl):
        acc, m, l = _gqa_sdpa_chunked(
            ql, kl, vl, window=window, q_pos=qpl, kv_pos=kpl, causal=True,
            return_state=True, allow_window_slice=False)
        # the max is a pure numerical shift: it cancels exactly in the
        # acc_g/l_g quotient, so stopping its gradient is analytically
        # correct (and pmax has no AD rule anyway)
        m_g = jax.lax.pmax(jax.lax.stop_gradient(m), axis)
        alpha = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * alpha, axis)
        acc_g = jax.lax.psum(acc * alpha, axis)
        out = acc_g / jnp.where(l_g == 0.0, 1.0, l_g)
        b, kvh, g, s, d = out.shape
        return out.reshape(b, kvh * g, s, d).astype(ql.dtype)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec), P(bspec, None, axis), P(bspec, None, axis),
                  P(), P(axis)),
        out_specs=P(bspec), check_vma=False)
    return f(q, k, v, q_pos, kv_pos)


def _context_parallel_axis(skv: int) -> str | None:
    """The mesh axis for context-parallel attention, if the rules enable it
    and the kv length divides."""
    c = sharding.current()
    if not c or c["mesh"] is None:
        return None
    axis = c["rules"].get("attn_context_parallel")
    if not axis:
        return None
    if skv % c["mesh"].shape.get(axis, 1) != 0:
        return None
    return axis


def _gqa_sdpa(q, k, v, *, mask_mode: str, window: int, q_pos, kv_pos) -> jax.Array:
    sq, skv = q.shape[2], k.shape[2]
    if sq >= 2048 and mask_mode != "none":
        axis = _context_parallel_axis(skv)
        if axis is not None and sq == skv:
            return _gqa_sdpa_context_parallel(q, k, v, window=window,
                                              q_pos=q_pos, kv_pos=kv_pos,
                                              axis=axis)
        return _gqa_sdpa_chunked(q, k, v, window=window, q_pos=q_pos,
                                 kv_pos=kv_pos, causal=True)
    return _gqa_sdpa_direct(q, k, v, mask_mode=mask_mode, window=window,
                            q_pos=q_pos, kv_pos=kv_pos)


POS_EMPTY = -(2 ** 30)  # pos sentinel for an empty cache slot (always masked)


@dataclasses.dataclass
class KVCache:
    """Decode cache for one attention layer.

    ``k, v``: [B, KV, S_cache, D].  ``pos``: [B, S_cache] token position
    held in each slot (-2^30 for empty: always masked out) — every batch
    row advances at its own absolute position, the one decode-state layout
    (lockstep decode is just the special case where all rows agree).  For
    sliding-window layers ``S_cache == window`` and slots are a ring buffer;
    for full attention ``S_cache`` is the max context.

    With ``cfg.kv_cache_dtype == "int8"``, ``k``/``v`` store int8 values
    with per-(batch, head, slot) symmetric scales in ``k_scale``/``v_scale``
    ([B, KV, S_cache] f32) — the paper's Sec. II-D quantization applied to
    the decode memory floor; dequantization fuses into the flash-decode
    Pallas kernel (kernels/decode_attention.py) so the HBM read is
    half-width.
    """
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @staticmethod
    def _wants_int8(cfg) -> bool:
        return getattr(cfg, "kv_cache_dtype", "") == "int8"

    @staticmethod
    def specs(cfg, batch: int, s_cache: int, dtype) -> "KVCache":
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        pshape = (batch, s_cache)
        if KVCache._wants_int8(cfg):
            return KVCache(
                k=jax.ShapeDtypeStruct((batch, kvh, s_cache, hd), jnp.int8),
                v=jax.ShapeDtypeStruct((batch, kvh, s_cache, hd), jnp.int8),
                pos=jax.ShapeDtypeStruct(pshape, jnp.int32),
                k_scale=jax.ShapeDtypeStruct((batch, kvh, s_cache), jnp.float32),
                v_scale=jax.ShapeDtypeStruct((batch, kvh, s_cache), jnp.float32),
            )
        return KVCache(
            k=jax.ShapeDtypeStruct((batch, kvh, s_cache, hd), dtype),
            v=jax.ShapeDtypeStruct((batch, kvh, s_cache, hd), dtype),
            pos=jax.ShapeDtypeStruct(pshape, jnp.int32),
        )

    @staticmethod
    def init(cfg, batch: int, s_cache: int, dtype) -> "KVCache":
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        pshape = (batch, s_cache)
        if KVCache._wants_int8(cfg):
            return KVCache(
                k=jnp.zeros((batch, kvh, s_cache, hd), jnp.int8),
                v=jnp.zeros((batch, kvh, s_cache, hd), jnp.int8),
                pos=jnp.full(pshape, POS_EMPTY, jnp.int32),
                k_scale=jnp.zeros((batch, kvh, s_cache), jnp.float32),
                v_scale=jnp.zeros((batch, kvh, s_cache), jnp.float32),
            )
        return KVCache(
            k=jnp.zeros((batch, kvh, s_cache, hd), dtype),
            v=jnp.zeros((batch, kvh, s_cache, hd), dtype),
            pos=jnp.full(pshape, POS_EMPTY, jnp.int32),
        )

    AXES = {"k": ("batch", "kv_heads", "kv_seq", "head_dim"),
            "v": ("batch", "kv_heads", "kv_seq", "head_dim"),
            "pos": ("batch", "kv_seq"),
            "k_scale": ("batch", "kv_heads", "kv_seq"),
            "v_scale": ("batch", "kv_heads", "kv_seq")}


jax.tree_util.register_dataclass(
    KVCache, ("k", "v", "pos", "k_scale", "v_scale"), ())


@dataclasses.dataclass
class PagedKVCache:
    """Block/paged decode cache for one attention layer (serving engine).

    ``k, v``: [n_pages, KV, page_size, D] — a pool of fixed-size pages
    shared by every serving slot.  ``pos``: [n_pages, page_size] absolute
    token position per page entry (-2^30 = empty).  ``page_table``:
    [n_slots, max_pages] physical page id per (slot, logical page); rows of
    unallocated slots hold the out-of-bounds sentinel ``n_pages`` so their
    scatter updates are dropped.  A slot's logical cache length is
    ``max_pages * page_size``; token position ``p`` lives at logical index
    ``p % logical_len`` (ring semantics — sliding-window layers wrap across
    page boundaries; the position-based mask keeps attention exact).

    Allocation/free of pages is host-side bookkeeping
    (``repro.serving.paged_kv.PageAllocator``); the device only ever sees
    scatter through the table — decode attention walks the table *inside*
    the fused Pallas kernel (kernels/paged_attention.py), so the same
    program serves any mix of request lengths at slot-sized HBM traffic,
    which is the serving-side restatement of the paper's
    one-uniform-dataflow thesis.

    With an int8 pool (``cfg.kv_cache_dtype == "int8"``), ``k``/``v`` hold
    int8 values with per-(page, head, offset) symmetric scales in
    ``k_scale``/``v_scale`` ([n_pages, KV, page_size] f32); dequantization
    fuses into the kernel's score/context dot products exactly like
    ``decode_attention``'s dense int8 path.
    """
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    page_table: jax.Array
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def logical_len(self) -> int:
        return self.page_table.shape[1] * self.k.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.shape[0]


jax.tree_util.register_dataclass(
    PagedKVCache, ("k", "v", "pos", "page_table", "k_scale", "v_scale"), ())


@dataclasses.dataclass
class AttnOutput:
    y: jax.Array
    cache: KVCache | None = None


def _gather_pool_view(cache: PagedKVCache, bsz: int, kvh: int, hd: int):
    """Per-slot contiguous view of the pool: (k, v [B, KV, L, D] — f32
    dequantized for int8 pools — and pos [B, L]).  Unallocated slots gather
    clamped garbage under positions their mask never admits."""
    logical = cache.logical_len
    kg = cache.k[cache.page_table]                         # [B,MP,KV,ps,D]
    vg = cache.v[cache.page_table]
    kg = kg.transpose(0, 2, 1, 3, 4).reshape(bsz, kvh, logical, hd)
    vg = vg.transpose(0, 2, 1, 3, 4).reshape(bsz, kvh, logical, hd)
    posg = cache.pos[cache.page_table].reshape(bsz, logical)
    if cache.quantized:
        ksg = cache.k_scale[cache.page_table].transpose(0, 2, 1, 3)
        vsg = cache.v_scale[cache.page_table].transpose(0, 2, 1, 3)
        kg = kg.astype(jnp.float32) * ksg.reshape(bsz, kvh, logical)[..., None]
        vg = vg.astype(jnp.float32) * vsg.reshape(bsz, kvh, logical)[..., None]
    return kg, vg, posg


def _paged_chunk(cfg, cache: PagedKVCache, q, k, v, *, positions, lengths,
                 window: int):
    """Prefill one chunk against a paged cache — the multi-token general
    case of paged decode (decode is the 1-token chunk; the serving engine's
    *mixed step* batches both phases through this one path).

    ``positions`` [B, S] are global: row ``b`` holds
    ``starts[b] + arange(S)`` and ``lengths[b]`` of the S tokens are real
    (0 for slots idle this step — their state is untouched).  Each row
    attends over its **already-written pages** plus the causal in-chunk
    block, then its valid K/V are scattered into the pages.  Attend before
    scatter: with ring wrap a chunk may evict positions that in-chunk
    queries still need (window W, chunk > logical: token ``p`` overwrites
    ``p - logical``, which earlier in-chunk queries are still inside W of),
    so the pool must be read pre-scatter and the in-chunk keys taken raw.
    Causality across the seam is positional: pool entries hold positions
    ``< starts[b]``, in-chunk pads sit at positions beyond the row's last
    real token, so ``kv_pos <= q_pos`` masks both exactly.
    """
    from repro.serving.paged_kv import scatter_prefill
    b, kvh, s, hd = k.shape
    if positions.ndim != 2:
        raise ValueError("paged chunk prefill needs per-slot [B, S] "
                         "positions (global: starts[b] + arange(S))")
    starts = positions[:, 0].astype(jnp.int32)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    lengths = lengths.astype(jnp.int32)

    kg, vg, posg = _gather_pool_view(cache, b, kvh, hd)
    # in-chunk keys: pads (j >= length) masked by the pos sentinel — their
    # positions are future anyway, but an idle row (length 0) has no valid
    # query to hide behind
    in_pos = jnp.where(jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None],
                       positions.astype(jnp.int32), POS_EMPTY)
    k_all = jnp.concatenate([kg, k.astype(kg.dtype)], axis=2)
    v_all = jnp.concatenate([vg, v.astype(vg.dtype)], axis=2)
    pos_all = jnp.concatenate([posg, in_pos], axis=1)
    # direct attention: chunks are small by design (that is the point of
    # chunking), and the flash dispatch assumes shared 1-D q_pos
    out = _gqa_sdpa_direct(q, k_all, v_all, mask_mode="causal", window=window,
                           q_pos=positions, kv_pos=pos_all).astype(q.dtype)

    ks = vs = None
    kq, vq = k, v
    if cache.quantized:
        from repro.kernels.decode_attention import quantize_kv
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
    dense = KVCache(k=kq, v=vq, pos=in_pos, k_scale=ks, v_scale=vs)
    new_cache = scatter_prefill(cache, dense, jnp.arange(b, dtype=jnp.int32),
                                lengths, starts=starts)
    return out, new_cache


def _paged_decode(cfg, cache: PagedKVCache, q, k, v, *, positions, window: int,
                  lengths=None):
    """One-token decode against a paged cache: scatter the new K/V into each
    slot's page, then attend **straight off the page pools** with the fused
    flash-decode kernel (kernels/paged_attention.py) — the page-table walk
    happens inside the kernel's grid, so no dense ``[B, KV, L, D]`` view is
    ever materialized on the hot path.

    The old full-table gather survives only as the reference implementation
    (mode ``"reference"``: the off-TPU default, and the oracle the property
    tests pin the kernel to); ``kernels.paged_attention.set_paged_decode_mode``
    / ``$KRAKEN_PAGED_DECODE`` select per process, the engine's
    ``decode_kernel=`` per program.

    ``positions`` must be per-slot [B, 1].  Unallocated slots carry the
    out-of-bounds page sentinel in their table row, so their scatters drop
    (``mode="drop"``) and their reads are skipped (fused) or clamped+masked
    (reference) — harmless, because the engine discards their logits and
    their pos mask never admits future reads.  ``lengths`` ([B], the mixed
    engine's per-row live mask) additionally drops the writes of rows with
    ``lengths == 0`` — a slot mid-*prefill* holds live table rows that a
    decode step it does not participate in must not touch.
    """
    from repro.kernels import paged_attention as _pa
    if positions.ndim != 2:
        raise ValueError("paged decode needs per-slot [B, 1] positions")
    if k.shape[2] != 1:
        raise ValueError("paged cache decode is one token per slot; chunk "
                         "prefill goes through _paged_chunk")
    bsz = q.shape[0]
    ps = cache.page_size
    logical = cache.logical_len
    pvec = positions[:, 0].astype(jnp.int32)                   # [B]
    li = pvec % logical                                        # ring slot
    rows = jnp.arange(bsz)
    pp = cache.page_table[rows, li // ps]                      # [B] phys page
    if lengths is not None:
        pp = jnp.where(lengths.astype(jnp.int32) > 0, pp, cache.n_pages)
    off = li % ps
    ksc = vsc = None
    if cache.quantized:
        from repro.kernels.decode_attention import quantize_kv
        k, ks_new = quantize_kv(k)
        v, vs_new = quantize_kv(v)
        ksc = cache.k_scale.at[pp, :, off].set(ks_new[:, :, 0], mode="drop")
        vsc = cache.v_scale.at[pp, :, off].set(vs_new[:, :, 0], mode="drop")
    ck = cache.k.at[pp, :, off].set(k[:, :, 0], mode="drop")
    cv = cache.v.at[pp, :, off].set(v[:, :, 0], mode="drop")
    cpos = cache.pos.at[pp, off].set(pvec, mode="drop")
    new_cache = PagedKVCache(k=ck, v=cv, pos=cpos,
                             page_table=cache.page_table,
                             k_scale=ksc, v_scale=vsc)

    mode = _pa.resolve_paged_decode_mode()
    if mode == "reference":
        kg, vg, posg = _gather_pool_view(new_cache, bsz, cfg.num_kv_heads,
                                         cfg.head_dim)
        out = _gqa_sdpa(q, kg, vg, mask_mode="causal", window=window,
                        q_pos=positions, kv_pos=posg)
    else:
        out = ops.kraken_paged_attention(
            q[:, :, 0], ck, cv, pos_pages=cpos,
            page_table=cache.page_table, q_pos=pvec,
            k_scale=ksc, v_scale=vsc, window=window,
            use_pallas=True, interpret=(mode == "interpret"))[:, :, None]
    return out, new_cache


def attention(cfg, params: Params, prefix: str, x: jax.Array, *,
              positions: jax.Array,
              window: int = 0,
              kv_x: jax.Array | None = None,        # cross-attn source
              cache: KVCache | None = None,
              causal: bool = True,
              lengths: jax.Array | None = None) -> AttnOutput:
    """One attention layer through the uniform-GEMM projections.

    Modes:
    * self-attention over x (train/prefill): kv_x and cache are None
    * cross-attention: kv_x given (no causal mask)
    * cached decode: cache given; x is the new token(s); positions [S_q]
      holds their absolute positions
    * chunk prefill: paged cache + S > 1 with per-slot [B, S] positions —
      attend over the already-written pages plus the causal in-chunk block,
      then append the chunk (``lengths`` [B] = real tokens per row; rows at
      0 are idle this step and stay untouched).
    """
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(x, params[f"{prefix}_wq"], bias=params.get(f"{prefix}_bq"))
    src = x if kv_x is None else kv_x
    k = dense(src, params[f"{prefix}_wk"], bias=params.get(f"{prefix}_bk"))
    v = dense(src, params[f"{prefix}_wv"], bias=params.get(f"{prefix}_bv"))
    q = _split_heads(q, h, hd)
    k = _split_heads(k, kv, hd)
    v = _split_heads(v, kv, hd)

    if cfg.positional == "rope" and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if isinstance(cache, PagedKVCache):
        if k.shape[2] == 1:
            out, new_cache = _paged_decode(cfg, cache, q, k, v,
                                           positions=positions, window=window,
                                           lengths=lengths)
        else:
            out, new_cache = _paged_chunk(cfg, cache, q, k, v,
                                          positions=positions, lengths=lengths,
                                          window=window)
    elif cache is not None:
        s_cache = cache.k.shape[2]
        s_new = k.shape[2]
        quant = cache.quantized
        if quant:
            from repro.kernels.decode_attention import quantize_kv
        if positions.ndim == 1:
            # Prefill (shared [S] positions, S >= 1): attend over the full
            # (windowed) sequence; the cache keeps the last s_cache tokens,
            # ring-rotated so slot == pos % s_cache (matching what decode's
            # single-slot updates produce).
            keep = min(s_new, s_cache)
            k_last = k[:, :, -keep:, :]
            v_last = v[:, :, -keep:, :]
            p_last = positions[-keep:].astype(jnp.int32)
            r = p_last[0] % s_cache
            ks = vs = None
            if quant:
                k_last, ks_new = quantize_kv(k_last)
                v_last, vs_new = quantize_kv(v_last)
                ks = jnp.roll(jax.lax.dynamic_update_slice_in_dim(
                    cache.k_scale, ks_new, 0, axis=2), r, axis=2)
                vs = jnp.roll(jax.lax.dynamic_update_slice_in_dim(
                    cache.v_scale, vs_new, 0, axis=2), r, axis=2)
            ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k_last, 0, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v_last, 0, axis=2)
            ck = jnp.roll(ck, r, axis=2)
            cv = jnp.roll(cv, r, axis=2)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache.pos,
                jnp.broadcast_to(p_last, (cache.pos.shape[0], keep)),
                0, axis=1)
            cpos = jnp.roll(cpos, r, axis=1)
            new_cache = KVCache(k=ck, v=cv, pos=cpos, k_scale=ks, v_scale=vs)
            out = _gqa_sdpa(q, k, v, mask_mode="causal", window=window,
                            q_pos=positions, kv_pos=positions)
        else:
            # Per-slot decode: every batch row inserts its token at its
            # *own* ring slot and masks at its own length (lockstep decode
            # is the special case where all rows carry the same position —
            # the scalar-position shim was removed with the legacy dense
            # serving loop).
            if s_new != 1:
                raise ValueError(
                    "per-slot positions with multi-token input: per-slot "
                    "prefill goes through the serving engine's bucketed "
                    "batched prefill, not the dense cache path")
            bsz = x.shape[0]
            pvec = positions[:, 0].astype(jnp.int32)          # [B]
            slots = pvec % s_cache                            # [B]
            rows = jnp.arange(bsz)
            ks = vs = None
            if quant:
                k, ks_new = quantize_kv(k)
                v, vs_new = quantize_kv(v)
                ks = cache.k_scale.at[rows, :, slots].set(ks_new[:, :, 0])
                vs = cache.v_scale.at[rows, :, slots].set(vs_new[:, :, 0])
            ck = cache.k.at[rows, :, slots].set(k[:, :, 0])
            cv = cache.v.at[rows, :, slots].set(v[:, :, 0])
            cpos = cache.pos.at[rows, slots].set(pvec)
            new_cache = KVCache(k=ck, v=cv, pos=cpos, k_scale=ks, v_scale=vs)
            if quant:
                from repro.kernels import ops as _ops
                out = _ops.kraken_decode_attention(
                    q[:, :, 0], ck, cv, k_scale=ks, v_scale=vs,
                    kv_pos=cpos, q_pos=pvec, window=window)[:, :, None]
            else:
                out = _gqa_sdpa(q, ck, cv, mask_mode="causal", window=window,
                                q_pos=positions, kv_pos=cpos)
    elif kv_x is not None:
        out = _gqa_sdpa(q, k, v, mask_mode="none", window=0,
                        q_pos=positions, kv_pos=jnp.arange(k.shape[2]))
    elif window and _use_kernels() and x.shape[1] % 128 == 0:
        out = ops.swa_attention(q, k, v, window=window, use_pallas=True)
    else:
        out = _gqa_sdpa(q, k, v, mask_mode="causal" if causal else "none",
                        window=window, q_pos=positions, kv_pos=positions)

    out = sharding.shard(out, "batch", "heads", "seq", "head_dim")
    y = dense(_merge_heads(out), params[f"{prefix}_wo"])
    return AttnOutput(y=y, cache=new_cache)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_specs(cfg, prefix: str = "mlp", d_ff: int | None = None) -> dict[str, Spec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp == "swiglu":
        return {
            f"{prefix}_wi_gate": Spec((d, f), ("embed", "mlp")),
            f"{prefix}_wi_up": Spec((d, f), ("embed", "mlp")),
            f"{prefix}_wo": Spec((f, d), ("mlp", "embed")),
        }
    return {
        f"{prefix}_wi": Spec((d, f), ("embed", "mlp")),
        f"{prefix}_bi": Spec((f,), ("mlp",), 0.0),
        f"{prefix}_wo": Spec((f, d), ("mlp", "embed")),
        f"{prefix}_bo": Spec((d,), ("embed",), 0.0),
    }


def mlp(cfg, params: Params, prefix: str, x: jax.Array) -> jax.Array:
    if cfg.mlp == "swiglu":
        gate = dense(x, params[f"{prefix}_wi_gate"], activation="silu")
        up = dense(x, params[f"{prefix}_wi_up"])
        h = sharding.shard(gate * up, "batch", "seq", "mlp")
        return dense(h, params[f"{prefix}_wo"])
    h = dense(x, params[f"{prefix}_wi"], bias=params[f"{prefix}_bi"], activation="gelu")
    h = sharding.shard(h, "batch", "seq", "mlp")
    return dense(h, params[f"{prefix}_wo"], bias=params[f"{prefix}_bo"])
