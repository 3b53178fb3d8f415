"""Fused paged-attention decode kernel (kernels/paged_attention.py) vs the
dense-gather reference — the lockdown for the serving hot path's only
non-GEMM kernel.

Covered (all in Pallas interpret mode — the real grid/BlockSpec/scalar-
prefetch structure, on CPU):

* property sweep: random shuffled page tables, ring-wrapped positions,
  empty-slot sentinel rows, sliding windows, and every ``pages_per_block``
  layout (incl. non-dividing ones that sentinel-pad the table) agree with
  the dense-gather oracle, for 1, 2 and 4 KV heads (a grid step holds every
  KV head of its pages) at group sizes 1, 4 and 8, f32 and bf16 pools;
* int8-quantized pools: in-kernel dequant == oracle, within the int8 error
  bound of the fp pool;
* ``_paged_decode`` end-to-end: the fused mode and the surviving dense-
  gather reference mode produce the same attention output *and* the same
  updated cache, scatter included (fp + int8 pools);
* ``scatter_prefill`` round-trips per-token scales into a quantized pool.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import quantize_kv
from repro.kernels.paged_attention import (default_pages_per_block,
                                           paged_decode_attention,
                                           use_paged_decode_mode)
from repro.models.layers import KVCache, POS_EMPTY, PagedKVCache, _paged_decode
from repro.serving import make_pool, scatter_prefill

CFG = SimpleNamespace(num_kv_heads=2, head_dim=8)
CFG8 = SimpleNamespace(num_kv_heads=2, head_dim=8, kv_cache_dtype="int8")
# (KV heads, query heads per KV head): the original geometry first, then
# every KV-head count of the sweep at every group size
GEOMS = [(2, 2)] + [(kv, g) for kv in (1, 2, 4) for g in (1, 4, 8)]
# one compile per shape instead of one per op: the sweep draws many shapes
_scatter = jax.jit(scatter_prefill)
_oracle = jax.jit(ref.paged_decode_attention, static_argnames=("window",))


def _cfg(kv_heads, quantized=False):
    base = CFG8 if quantized else CFG
    return SimpleNamespace(**{**vars(base), "num_kv_heads": kv_heads})


def _build_pool(rng, cfg, n_slots, ps, mp, lengths, *, quantized=False,
                dtype=jnp.float32):
    """A pool in the state token-by-token serving leaves it: shuffled
    physical pages, per-slot ring contents for ``lengths`` (None = slot
    never allocated -> sentinel table row), positions exact.

    Returns (pool, dense_history) where dense_history is the position-
    identity fp cache the contents were scattered from.
    """
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    logical = ps * mp
    n_pages = n_slots * mp + 1          # one spare page: never referenced
    table = np.full((n_slots, mp), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    pi = 0
    for b, ln in enumerate(lengths):
        if ln is None:
            continue
        table[b] = perm[pi:pi + mp]
        pi += mp
    pool = make_pool(cfg, n_pages=n_pages, page_size=ps, max_pages=mp,
                     n_slots=n_slots, dtype=dtype)
    pool = dataclasses.replace(pool, page_table=jnp.asarray(table))

    s = max((ln or 1) for ln in lengths)
    kf = jnp.asarray(rng.normal(size=(n_slots, kvh, s, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(n_slots, kvh, s, hd)), jnp.float32)
    ks = vs = None
    if quantized:
        kq, ks = quantize_kv(kf)
        vq, vs = quantize_kv(vf)
        dense = KVCache(k=kq, v=vq, pos=jnp.arange(s, dtype=jnp.int32),
                        k_scale=ks, v_scale=vs)
    else:
        dense = KVCache(k=kf.astype(dtype), v=vf.astype(dtype),
                        pos=jnp.arange(s, dtype=jnp.int32))
    lens = jnp.asarray([0 if ln is None else ln for ln in lengths], jnp.int32)
    pool = _scatter(pool, dense, jnp.arange(n_slots), lens)
    return pool, KVCache(k=kf, v=vf, pos=jnp.arange(s, dtype=jnp.int32))


def _q_and_pos(rng, cfg, lengths, *, group=2, dtype=jnp.float32):
    n_slots = len(lengths)
    q = jnp.asarray(rng.normal(
        size=(n_slots, group * cfg.num_kv_heads, cfg.head_dim)), dtype)
    q_pos = jnp.asarray([max(0, (ln or 1) - 1) for ln in lengths], jnp.int32)
    return q, q_pos


def _fused_vs_ref(pool, q, q_pos, *, window, ppb):
    got = paged_decode_attention(
        q, pool.k, pool.v, pos_pages=pool.pos, page_table=pool.page_table,
        q_pos=q_pos, k_scale=pool.k_scale, v_scale=pool.v_scale,
        window=window, pages_per_block=ppb, interpret=True)
    want = _oracle(
        q, pool.k, pool.v, pos_pages=pool.pos, page_table=pool.page_table,
        q_pos=q_pos, k_scale=pool.k_scale, v_scale=pool.v_scale,
        window=window)
    return got, want


@pytest.mark.parametrize("kv_heads,group", GEOMS)
@settings(max_examples=4, deadline=None)
@given(page_size=st.integers(1, 4), max_pages=st.integers(1, 3),
       n_slots=st.integers(1, 3), window=st.sampled_from([0, 1, 3]),
       ppb=st.integers(1, 4), seed=st.integers(0, 99))
def test_fused_matches_gather_reference(kv_heads, group, page_size,
                                        max_pages, n_slots, window, ppb,
                                        seed):
    """Random tables / ring wrap / sentinel slots / windows / block layouts,
    per KV-head count and group size: the fused kernel is the dense-gather
    reference, to float tolerance."""
    rng = np.random.default_rng(seed)
    cfg = _cfg(kv_heads)
    logical = page_size * max_pages
    lengths = [None if (n_slots > 1 and rng.integers(4) == 0)
               else int(rng.integers(1, 3 * logical + 1))
               for _ in range(n_slots)]
    pool, _ = _build_pool(rng, cfg, n_slots, page_size, max_pages, lengths)
    q, q_pos = _q_and_pos(rng, cfg, lengths, group=group)
    got, want = _fused_vs_ref(pool, q, q_pos, window=window, ppb=ppb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kv_heads,group", [(2, 2), (1, 8), (4, 4)])
def test_every_pages_per_block_layout_agrees(kv_heads, group, dtype, tol):
    """ppb from 1 to beyond the table (sentinel padding) — one answer, for
    each KV-head count and pool dtype.  A bf16 pool rounds the softmax
    weights to bf16 before ``p . v``; the oracle is exact f32."""
    rng = np.random.default_rng(5)
    cfg = _cfg(kv_heads)
    lengths = [11, 3, None, 25]
    pool, _ = _build_pool(rng, cfg, 4, 3, 3, lengths,   # logical 9: wraps
                          dtype=dtype)
    q, q_pos = _q_and_pos(rng, cfg, lengths, group=group, dtype=dtype)
    outs = []
    default = default_pages_per_block(
        3, 3, kv_heads=kv_heads, head_dim=cfg.head_dim,
        itemsize=jnp.dtype(dtype).itemsize)
    for ppb in [1, 2, 3, 4, default]:
        got, want = _fused_vs_ref(pool, q, q_pos, window=4, ppb=ppb)
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        outs.append(got)
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=tol, atol=tol)


@pytest.mark.parametrize("kv_heads,group", [(2, 2), (1, 8), (4, 4)])
@settings(max_examples=6, deadline=None)
@given(page_size=st.integers(1, 4), max_pages=st.integers(1, 3),
       window=st.sampled_from([0, 2]), seed=st.integers(0, 99))
def test_fused_int8_pool(kv_heads, group, page_size, max_pages, window,
                         seed):
    """Quantized pools: fused in-kernel dequant == the quantized oracle,
    and within the int8 error bound of the fp pool."""
    rng = np.random.default_rng(seed)
    cfg, cfg8 = _cfg(kv_heads), _cfg(kv_heads, quantized=True)
    logical = page_size * max_pages
    lengths = [int(rng.integers(1, 2 * logical + 1)) for _ in range(2)]
    # identical draws for both pools: same pages, same K/V history
    pool8, _ = _build_pool(np.random.default_rng(seed + 1), cfg8, 2,
                           page_size, max_pages, lengths, quantized=True)
    poolf, _ = _build_pool(np.random.default_rng(seed + 1), cfg, 2,
                           page_size, max_pages, lengths)
    assert pool8.quantized and pool8.k.dtype == jnp.int8
    q, q_pos = _q_and_pos(rng, cfg, lengths, group=group)
    got, want = _fused_vs_ref(pool8, q, q_pos, window=window, ppb=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    exact = _oracle(
        q, poolf.k, poolf.v, pos_pages=poolf.pos,
        page_table=poolf.page_table, q_pos=q_pos, window=window)
    assert float(jnp.abs(got - exact).max()) < 3e-2


def test_scatter_prefill_carries_scales():
    """int8 prefill scatter: every retained position's per-(head, token)
    scale lands at its page offset (and only there)."""
    rng = np.random.default_rng(3)
    ps, mp, ln = 2, 2, 3                      # logical 4, length 3
    pool, _ = _build_pool(rng, CFG8, 1, ps, mp, [ln], quantized=True)
    kvh, hd = CFG8.num_kv_heads, CFG8.head_dim
    kf = jnp.asarray(rng.normal(size=(1, kvh, ln, hd)), jnp.float32)
    _, ks = quantize_kv(kf)
    tbl = np.asarray(pool.page_table)
    k_scale = np.asarray(pool.k_scale)
    pos = np.asarray(pool.pos)
    for j in range(ln):
        pg, off = tbl[0, j // ps], j % ps
        assert pos[pg, off] == j
        assert (k_scale[pg, :, off] > 0).all()
    # unwritten offsets keep the zero init
    pg, off = tbl[0, ln // ps], ln % ps
    assert (k_scale[pg, :, off] == 0).all() and pos[pg, off] == POS_EMPTY


@pytest.mark.parametrize("quantized,kv_heads", [
    pytest.param(False, 2, id="False"), pytest.param(True, 2, id="True"),
    pytest.param(False, 1, id="False-kv1"),
    pytest.param(True, 4, id="True-kv4")])
def test_paged_decode_fused_equals_reference_mode(quantized, kv_heads):
    """_paged_decode end-to-end: token scatter + attention through the
    fused kernel == the surviving dense-gather reference mode — same
    output, same updated pool (values, positions, scales)."""
    rng = np.random.default_rng(9)
    cfg = _cfg(kv_heads, quantized)
    lengths = [5, 12, None]
    pool, _ = _build_pool(rng, cfg, 3, 2, 3, lengths, quantized=quantized)
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.normal(size=(3, 2 * kvh, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, kvh, 1, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, kvh, 1, hd)), jnp.float32)
    positions = jnp.asarray([[5], [12], [0]], jnp.int32)

    outs, caches = {}, {}
    for mode in ("reference", "interpret"):
        with use_paged_decode_mode(mode):
            out, new_cache = _paged_decode(cfg, pool, q, k, v,
                                           positions=positions, window=4)
        outs[mode] = np.asarray(out)
        caches[mode] = new_cache
    # live slots agree (the dead slot's output is discarded by the engine:
    # the reference clamp-gathers garbage there, the fused kernel zeros it)
    np.testing.assert_allclose(outs["interpret"][:2], outs["reference"][:2],
                               rtol=1e-5, atol=1e-5)
    for leaf_f, leaf_r in zip(jax.tree.leaves(caches["interpret"]),
                              jax.tree.leaves(caches["reference"])):
        np.testing.assert_array_equal(np.asarray(leaf_f), np.asarray(leaf_r))
    # the scatter landed: position 5 and 12 resident, ring-wrapped
    pos = np.asarray(caches["interpret"].pos)
    tbl = np.asarray(pool.page_table)
    for b, p in [(0, 5), (1, 12)]:
        li = p % pool.logical_len
        assert pos[tbl[b, li // pool.page_size], li % pool.page_size] == p


def test_ops_wrapper_reference_fallback_off_tpu():
    """ops.kraken_paged_attention without use_pallas/interpret flags routes
    to the jnp reference off-TPU (the serving default) and matches the
    kernel."""
    rng = np.random.default_rng(11)
    lengths = [7, 2]
    pool, _ = _build_pool(rng, CFG, 2, 2, 2, lengths)
    q, q_pos = _q_and_pos(rng, CFG, lengths)
    via_ops = ops.kraken_paged_attention(
        q, pool.k, pool.v, pos_pages=pool.pos, page_table=pool.page_table,
        q_pos=q_pos, window=3)
    got, _ = _fused_vs_ref(pool, q, q_pos, window=3, ppb=2)
    np.testing.assert_allclose(np.asarray(via_ops), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
