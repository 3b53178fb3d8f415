"""Empirical tile search: time real kernels, keep the measured winner.

The static model in :mod:`repro.core.elastic` ranks tile candidates by
closed-form utilization and modeled HBM traffic — the paper's eq. 19
reasoning.  MPNA and Chain-NN both document how such analytical rankings
diverge from measured performance once a real memory system is involved, so
this module closes the loop: it takes the model's top candidates (both
schedules) and runs each through the *actual* ``kraken_gemm`` /
``kraken_conv2d_direct`` Pallas kernels with warmup and
``block_until_ready``, keeping the fastest.

On TPU the kernels run natively; elsewhere they run in Pallas interpret
mode, which still exercises the genuine grid/BlockSpec structure per
candidate (the cache records the backend so measurements never leak across
substrates — see :mod:`repro.tuning.cache`).
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import numpy as np

from repro.core import elastic
from repro.core.elastic import TileConfig
from repro.tuning import cache as tcache


def _on_tpu() -> bool:
    from repro.kernels import ops
    return ops._on_tpu()


def backend_name() -> str:
    import jax
    b = jax.default_backend()
    return b if b == "tpu" else f"{b}-interpret"


@dataclasses.dataclass(frozen=True)
class Timing:
    config: TileConfig
    us: float              # median wall-clock microseconds per call


def shortlist(candidates: list[TileConfig], top_n: int = 4) -> list[TileConfig]:
    """Model-guided shortlist: top-N candidates *per schedule*.

    Taking the top-N of each schedule (rather than globally) guarantees the
    measurement always gets to arbitrate the weight-stationary vs
    output-stationary question — the one the static model is least equipped
    to answer, since it prices a VMEM-resident accumulator at zero.
    """
    ranked = sorted(candidates, key=lambda c: (c.utilization, -c.hbm_words),
                    reverse=True)
    out: list[TileConfig] = []
    per_sched: dict[str, int] = {}
    for cfg in ranked:
        if per_sched.get(cfg.schedule, 0) >= top_n:
            continue
        per_sched[cfg.schedule] = per_sched.get(cfg.schedule, 0) + 1
        out.append(cfg)
    return out


def select_candidates(m: int, k: int, n: int, *, in_bytes: int = 2,
                      top_n: int = 4) -> list[TileConfig]:
    """Enumerate the model's candidate lattice and shortlist it."""
    return shortlist(elastic.enumerate_tiles(m, k, n, in_bytes=in_bytes),
                     top_n)


def run_gemm_candidate(a, b, cfg: TileConfig, *, interpret: bool):
    """One ``kraken_gemm`` launch under candidate ``cfg``.

    Pads and slices with the hot path's own helper (``ops._pad_to``) so the
    measurement executes exactly what ``kraken_matmul`` would.
    """
    from repro.kernels.kraken_gemm import kraken_gemm
    from repro.kernels.ops import _pad_to
    m, _ = a.shape
    _, n = b.shape
    ap = _pad_to(a, (cfg.bm, cfg.bk))
    bp = _pad_to(b, (cfg.bk, cfg.bn))
    bk = ap.shape[1] if cfg.schedule == "weight_stationary" else cfg.bk
    out = kraken_gemm(ap, bp, bm=cfg.bm, bk=bk, bn=cfg.bn,
                      schedule=cfg.schedule, interpret=interpret)
    return out[:m, :n]


def time_gemm_candidate(m: int, k: int, n: int, cfg: TileConfig, *,
                        dtype=None, reps: int = 3, warmup: int = 1,
                        interpret: bool | None = None,
                        seed: int = 0) -> float:
    """Median microseconds per call for one candidate, properly synced."""
    import jax
    import jax.numpy as jnp
    if interpret is None:
        interpret = not _on_tpu()
    dtype = dtype or (jnp.bfloat16 if _on_tpu() else jnp.float32)
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    f = jax.jit(lambda a, b: run_gemm_candidate(a, b, cfg,
                                                interpret=interpret))
    for _ in range(max(warmup, 1)):        # compile + cold caches
        jax.block_until_ready(f(a, b))
    samples = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a, b))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def benchmark_candidates(m: int, k: int, n: int,
                         candidates: list[TileConfig], *,
                         dtype=None, reps: int = 3,
                         warmup: int = 1,
                         interpret: bool | None = None) -> list[Timing]:
    """Time every candidate; returns timings sorted fastest-first."""
    timings = [Timing(cfg, time_gemm_candidate(
        m, k, n, cfg, dtype=dtype, reps=reps, warmup=warmup,
        interpret=interpret)) for cfg in candidates]
    return sorted(timings, key=lambda t: t.us)


def autotune_gemm(m: int, k: int, n: int, *, in_bytes: int | None = None,
                  dtype_name: str | None = None,
                  op_kind: str = "gemm",
                  top_n: int = 4, reps: int = 3,
                  candidates: list[TileConfig] | None = None,
                  cache: tcache.TileCache | None = None,
                  log=None) -> TileConfig:
    """Measured tile selection for one GEMM cell, with cache write-through.

    Cache hit: return the persisted winner (no measurement).  Miss: shortlist
    (from ``candidates`` if the caller already enumerated them — e.g. under a
    non-default VMEM budget — else from the model's default lattice), time
    each on the real kernel, persist the fastest (alongside the model's own
    pick, so ``autotune_report`` can show where measurement overturned the
    model) and return it.

    ``in_bytes`` defaults to the itemsize of ``dtype_name`` so the VMEM
    feasibility filter prices tiles in the dtype actually being measured
    (an fp32 tile is twice a bf16 one).
    """
    import jax.numpy as jnp
    from repro import tuning
    if cache is None:
        cache = tcache.TileCache(path=None)
    dtype_name = dtype_name or ("bfloat16" if _on_tpu() else "float32")
    if in_bytes is None:
        in_bytes = jnp.dtype(dtype_name).itemsize
    key = tcache.cache_key(op_kind, m, k, n, dtype_name, backend_name())
    hit = cache.get(key)
    if hit is not None:
        return hit
    if candidates is None:
        candidates = elastic.enumerate_tiles(m, k, n, in_bytes=in_bytes)
    candidates = shortlist(candidates, top_n)
    modeled = elastic.model_best(candidates)
    if not _on_tpu() and m * k * n > tuning.INTERPRET_MACS_CAP:
        # Production-sized cell on an interpret backend: a single candidate
        # run would take minutes to hours.  Fall back to the model pick
        # (uncached, so a real TPU run still gets to measure it).
        if log is not None:
            log(f"[autotune] {key}: skipped — {m * k * n:.2e} MACs exceeds "
                f"the interpret-mode cap; using the model pick (warm this "
                f"cell on TPU)")
        return modeled
    timings = benchmark_candidates(m, k, n, candidates, reps=reps,
                                   dtype=jnp.dtype(dtype_name).type)
    winner = timings[0]
    cache.put(key, winner.config, measured_us=winner.us, extra={
        "model_pick": dataclasses.asdict(modeled),
        "candidates_timed": len(timings),
        "agrees_with_model": _same_plan(winner.config, modeled),
    })
    cache.save()
    if log is not None:
        log(f"[autotune] {key}: winner ({winner.config.bm},{winner.config.bk},"
            f"{winner.config.bn})/{winner.config.schedule} "
            f"{winner.us:.0f}us over {len(timings)} candidates "
            f"(model {'agrees' if _same_plan(winner.config, modeled) else 'overruled'})")
    return winner.config


def _same_plan(a: TileConfig, b: TileConfig) -> bool:
    return (a.bm, a.bk, a.bn, a.schedule) == (b.bm, b.bk, b.bn, b.schedule)


def paged_decode_candidates(page_size: int, max_pages: int, *,
                            kv_heads: int, head_dim: int,
                            itemsize: int) -> list[int]:
    """The ``pages_per_block`` lattice for the fused paged-decode kernel:
    every power of two up to the whole table, the table itself, and the
    static default — each within the kernel's VMEM budget for a step that
    holds every KV head of its pages."""
    from repro.kernels.paged_attention import (default_pages_per_block,
                                               max_pages_per_block)
    geom = dict(kv_heads=kv_heads, head_dim=head_dim, itemsize=itemsize)
    cap = min(max_pages, max_pages_per_block(page_size, **geom))
    cands = {max_pages, default_pages_per_block(page_size, max_pages, **geom)}
    ppb = 1
    while ppb <= max_pages:
        cands.add(ppb)
        ppb *= 2
    return sorted(c for c in cands if 1 <= c <= cap)


def lookup_paged_decode(cache: tcache.TileCache, key: str, *,
                        page_size: int, kv_heads: int, max_pages: int,
                        count: bool = True) -> int | None:
    """A validated ``paged_decode`` cache hit, or None.

    The key's ``m/k/n`` (slots/logical_len/head_dim) under-determines the
    cell: the same logical length can be built from different page sizes,
    and a ``pages_per_block`` tuned for 8-token pages means nothing for
    16-token ones.  The entry records its ``page_size`` and ``kv_heads``
    (a step fetches every KV head of its pages, so the head count sets the
    bytes a ppb moves); a mismatch, or an entry without them (one tuned
    for a grid step per KV head), is a miss, and autotune then re-measures
    for the layout actually being served.
    ``count=False`` peeks without touching the hit/miss counters (status
    reporting around a call that will do its own counted lookup).
    """
    entry = cache.peek(key)
    if (not entry or entry.get("page_size") != page_size
            or entry.get("kv_heads") != kv_heads):
        if entry is not None and count:
            cache.misses += 1
        return None
    try:
        ppb = int(entry["bn"])
    except (KeyError, TypeError, ValueError):
        return None
    if count:
        cache.hits += 1
    return max(1, min(ppb, max_pages))


def steady_state_pool(slots: int, logical_len: int, head_dim: int, *,
                      page_size: int, kv_heads: int = 1,
                      q_heads: int | None = None,
                      dtype_name: str = "float32", seed: int = 0):
    """A page pool at serving steady state: every slot full (ring at
    ``q_pos = logical_len - 1``), shuffled physical pages, position-exact
    rows — the one fixture the paged-decode autotuner times and the kernel
    benchmarks reuse (a layout change here updates both).

    Returns ``(q, k, v, pos_pages, page_table, q_pos, k_scale, v_scale)``;
    the scales are ``None`` unless ``dtype_name == "int8"``.  A
    ``logical_len`` that page-size does not divide gets a ceil-sized table
    whose tail offsets stay empty (the engine's pools are page-aligned by
    construction; this keeps the public API crash-free off that path).
    """
    import jax.numpy as jnp
    q_heads = q_heads or kv_heads
    max_pages = max(1, -(-logical_len // max(1, page_size)))
    rng = np.random.default_rng(seed)
    n_pages = slots * max_pages
    table = jnp.asarray(
        rng.permutation(n_pages).reshape(slots, max_pages), jnp.int32)
    vals_k = rng.normal(size=(n_pages, kv_heads, page_size, head_dim))
    vals_v = rng.normal(size=(n_pages, kv_heads, page_size, head_dim))
    ksc = vsc = None
    if dtype_name == "int8":
        k = jnp.asarray(np.clip(np.round(vals_k * 40), -127, 127), jnp.int8)
        v = jnp.asarray(np.clip(np.round(vals_v * 40), -127, 127), jnp.int8)
        sc_shape = (n_pages, kv_heads, page_size)
        ksc = jnp.asarray(rng.uniform(0.01, 0.1, sc_shape), jnp.float32)
        vsc = jnp.asarray(rng.uniform(0.01, 0.1, sc_shape), jnp.float32)
        q_dt = jnp.float32
    else:
        q_dt = jnp.dtype(dtype_name)
        k = jnp.asarray(vals_k, q_dt)
        v = jnp.asarray(vals_v, q_dt)
    from repro.kernels.paged_attention import POS_EMPTY
    pos = np.full((n_pages, page_size), POS_EMPTY, np.int32)
    tbl_np = np.asarray(table)
    idx = np.arange(logical_len)
    for b in range(slots):
        pos[tbl_np[b, idx // page_size], idx % page_size] = idx
    q = jnp.asarray(rng.normal(size=(slots, q_heads, head_dim)), q_dt)
    q_pos = jnp.full((slots,), logical_len - 1, jnp.int32)
    return q, k, v, jnp.asarray(pos), table, q_pos, ksc, vsc


def autotune_paged_decode(slots: int, logical_len: int, head_dim: int, *,
                          page_size: int, kv_heads: int = 1,
                          q_heads: int | None = None, window: int = 0,
                          dtype_name: str | None = None, reps: int = 3,
                          warmup: int = 1,
                          cache: tcache.TileCache | None = None,
                          log=None) -> int:
    """Measured ``pages_per_block`` for the fused paged-decode kernel.

    Keyed ``op_kind="paged_decode"`` with ``m/k/n`` <- slots / logical_len /
    head_dim (the decode cell's identity); the winning ``pages_per_block``
    is recorded in the entry's ``bn`` field, the same convention as
    ``conv_direct``'s ``bco``.  The measurement serves a steady-state pool:
    every slot full (ring at ``q_pos = logical_len - 1``), shuffled physical
    pages — the block-layout question the static model cannot answer.
    Returns the winning ``pages_per_block``.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import paged_decode_attention
    if cache is None:
        cache = tcache.TileCache(path=None)
    dtype_name = dtype_name or ("bfloat16" if _on_tpu() else "float32")
    max_pages = max(1, -(-logical_len // max(1, page_size)))
    key = tcache.cache_key("paged_decode", slots, logical_len, head_dim,
                           dtype_name, backend_name())
    hit = lookup_paged_decode(cache, key, page_size=page_size,
                              kv_heads=kv_heads, max_pages=max_pages)
    if hit is not None:
        return hit
    q_heads = q_heads or kv_heads
    geom = dict(kv_heads=kv_heads, head_dim=head_dim,
                itemsize=jnp.dtype(dtype_name).itemsize)
    from repro import tuning
    if (not _on_tpu()
            and slots * q_heads * logical_len * head_dim
            > tuning.INTERPRET_MACS_CAP):
        from repro.kernels.paged_attention import default_pages_per_block
        if log is not None:
            log(f"[autotune] {key}: skipped — interpret-mode cap; using the "
                f"static pages_per_block (warm this cell on TPU)")
        return default_pages_per_block(page_size, max_pages, **geom)

    interpret = not _on_tpu()
    q, k, v, pos, table, q_pos, ksc, vsc = steady_state_pool(
        slots, logical_len, head_dim, page_size=page_size,
        kv_heads=kv_heads, q_heads=q_heads, dtype_name=dtype_name)

    candidates = paged_decode_candidates(page_size, max_pages, **geom)
    best_ppb, best_us = candidates[0], float("inf")
    for ppb in candidates:
        f = jax.jit(lambda q, k, v, pos, table, q_pos, ksc, vsc, ppb=ppb:
                    paged_decode_attention(
                        q, k, v, pos_pages=pos, page_table=table,
                        q_pos=q_pos, k_scale=ksc, v_scale=vsc,
                        window=window, pages_per_block=ppb,
                        interpret=interpret))
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(f(q, k, v, pos, table, q_pos, ksc, vsc))
        samples = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v, pos, table, q_pos, ksc, vsc))
            samples.append((time.perf_counter() - t0) * 1e6)
        us = statistics.median(samples)
        if us < best_us:
            best_ppb, best_us = ppb, us
    cfg = elastic._make_config(slots, logical_len, head_dim, elastic.SUBLANE,
                               elastic.round_up(logical_len, elastic.MXU_DIM),
                               best_ppb, "output_stationary", 4)
    cache.put(key, cfg, measured_us=best_us,
              extra={"candidates_timed": len(candidates),
                     "kind": "paged_decode_ppb", "page_size": page_size,
                     "kv_heads": kv_heads, "window": window})
    cache.save()
    if log is not None:
        log(f"[autotune] {key}: pages_per_block={best_ppb} {best_us:.0f}us "
            f"over {len(candidates)} candidates")
    return best_ppb


def moe_gemm_candidates(rows_per_group: int, dtype_name: str) -> list[int]:
    """The ``block_rows`` lattice for the grouped expert GEMM: every
    sublane-multiple power of two up to the (rounded) group, the whole
    group, and the static default."""
    from repro.kernels.kraken_moe_gemm import (_sublane, default_block_rows)
    sub = _sublane(dtype_name)
    cap = elastic.round_up(max(1, rows_per_group), sub)
    cands = {cap, default_block_rows(rows_per_group, dtype_name)}
    bm = sub
    while bm <= cap:
        cands.add(bm)
        bm *= 2
    return sorted(c for c in cands if sub <= c <= cap)


def lookup_moe_gemm(cache: tcache.TileCache, key: str, *, experts: int,
                    rows_per_group: int, dtype_name: str = "float32",
                    count: bool = True) -> int | None:
    """A validated ``moe_gemm`` cache hit, or None.

    The key's ``m/k/n`` (m_total/d/f) under-determines the cell: the same
    total row count can come from different expert counts, and a
    ``block_rows`` tuned for 8 groups of 64 means nothing for 64 groups of
    8.  The entry records its ``experts``; a mismatch is a miss (same
    protocol as ``lookup_paged_decode``'s ``page_size`` guard).
    """
    entry = cache.peek(key)
    if not entry or entry.get("experts") != experts:
        if entry is not None and count:
            cache.misses += 1
        return None
    try:
        bm = int(entry["bm"])
    except (KeyError, TypeError, ValueError):
        return None
    if count:
        cache.hits += 1
    from repro.kernels.kraken_moe_gemm import _sublane
    sub = _sublane(dtype_name)
    return max(sub, min(bm, elastic.round_up(max(1, rows_per_group), sub)))


def skewed_group_sizes(experts: int, rows_per_group: int,
                       seed: int = 0) -> np.ndarray:
    """A decode-shaped group table: a few hot experts, some empty — the
    load the grouped kernel's dead-block skip is built for.  The one
    fixture the moe_gemm autotuner times and the bench model reuses."""
    rng = np.random.default_rng(seed)
    raw = rng.zipf(1.5, size=experts).astype(np.float64)
    sizes = np.minimum((raw / raw.max() * rows_per_group).astype(np.int32),
                       rows_per_group)
    sizes[rng.random(experts) < 0.25] = 0
    if sizes.max() == 0:
        sizes[0] = max(1, rows_per_group // 2)
    return sizes.astype(np.int32)


def autotune_moe_gemm(experts: int, m_total: int, d: int, f: int, *,
                      dtype_name: str | None = None, reps: int = 3,
                      warmup: int = 1,
                      cache: tcache.TileCache | None = None,
                      log=None) -> int:
    """Measured ``block_rows`` for the grouped expert GEMM.

    Keyed ``op_kind="moe_gemm"`` with ``m/k/n`` <- m_total / d / f (the
    grouped cell's identity; ``experts`` rides in the entry and is
    validated on lookup).  The winning ``block_rows`` is recorded in the
    entry's ``bm`` field.  The measurement serves a skewed steady-state
    group table (hot + empty experts) — the dead-block layout question the
    static model cannot answer.  Returns the winning ``block_rows``.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.kraken_moe_gemm import grouped_moe_gemm
    if cache is None:
        cache = tcache.TileCache(path=None)
    dtype_name = dtype_name or ("bfloat16" if _on_tpu() else "float32")
    rows = max(1, -(-m_total // max(1, experts)))
    key = tcache.cache_key("moe_gemm", m_total, d, f, dtype_name,
                           backend_name())
    hit = lookup_moe_gemm(cache, key, experts=experts, rows_per_group=rows,
                          dtype_name=dtype_name)
    if hit is not None:
        return hit
    from repro import tuning
    from repro.kernels.kraken_moe_gemm import default_block_rows
    if not _on_tpu() and m_total * d * f > tuning.INTERPRET_MACS_CAP:
        if log is not None:
            log(f"[autotune] {key}: skipped — interpret-mode cap; using the "
                f"static block_rows (warm this cell on TPU)")
        return default_block_rows(rows, dtype_name)

    interpret = not _on_tpu()
    rng = np.random.default_rng(0)
    if dtype_name == "int8":
        xs = jnp.asarray(rng.integers(-127, 128, (experts, rows, d)),
                         jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (experts, d, f)), jnp.int8)
    else:
        dt = jnp.dtype(dtype_name)
        xs = jnp.asarray(rng.normal(size=(experts, rows, d)), dt)
        w = jnp.asarray(rng.normal(size=(experts, d, f)), dt)
    sizes = jnp.asarray(skewed_group_sizes(experts, rows), jnp.int32)

    candidates = moe_gemm_candidates(rows, dtype_name)
    best_bm, best_us = candidates[0], float("inf")
    for bm in candidates:
        fn = jax.jit(lambda xs, w, sizes, bm=bm: grouped_moe_gemm(
            xs, w, sizes, block_rows=bm, interpret=interpret))
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn(xs, w, sizes))
        samples = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(xs, w, sizes))
            samples.append((time.perf_counter() - t0) * 1e6)
        us = statistics.median(samples)
        if us < best_us:
            best_bm, best_us = bm, us
    cfg = elastic._make_config(m_total, d, f, best_bm,
                               elastic.round_up(d, elastic.MXU_DIM),
                               min(elastic.round_up(f, 128), 128),
                               "output_stationary", 4)
    cache.put(key, cfg, measured_us=best_us,
              extra={"candidates_timed": len(candidates),
                     "kind": "moe_gemm_bm", "experts": experts})
    cache.save()
    if log is not None:
        log(f"[autotune] {key}: block_rows={best_bm} {best_us:.0f}us "
            f"over {len(candidates)} candidates")
    return best_bm


def conv_cache_key(x_shape, k_shape,
                   stride: tuple[int, int]) -> tuple[str, int, int, int]:
    """The ``conv_direct`` cache key for a (pre-padded) conv geometry.

    Shared by :func:`autotune_conv` and the kernel-side lookup in
    ``kraken_conv._resolve_bco`` so the key derivation cannot drift.
    Returns ``(key, m_eq, k_eq, c_o)`` — the im2col-equivalent GEMM dims.
    """
    n, h, w, c_i = x_shape
    k_h, k_w, _, c_o = k_shape
    oh = (h - k_h) // stride[0] + 1
    ow = (w - k_w) // stride[1] + 1
    m_eq, k_eq = n * oh * ow, c_i * k_h * k_w
    key = tcache.cache_key("conv_direct", m_eq, k_eq, c_o, "float32",
                           backend_name())
    return key, m_eq, k_eq, c_o


def autotune_conv(x_shape: tuple[int, int, int, int],
                  k_shape: tuple[int, int, int, int], *,
                  stride: tuple[int, int] = (1, 1),
                  reps: int = 2,
                  cache: tcache.TileCache | None = None,
                  log=None) -> int:
    """Measured ``bco`` selection for the direct Kraken-dataflow conv kernel.

    Keyed by the conv's im2col-equivalent GEMM geometry under
    ``op_kind="conv_direct"``; the winning ``bco`` is recorded in the entry's
    ``bn`` field (the output-channel tile is the conv analogue of bn).
    Returns the winning ``bco``.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.kraken_conv import kraken_conv2d_direct
    if cache is None:
        cache = tcache.TileCache(path=None)
    key, m_eq, k_eq, c_o = conv_cache_key(x_shape, k_shape, stride)
    hit = cache.get(key)
    if hit is not None:
        return hit.bn
    interpret = not _on_tpu()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=x_shape), jnp.float32)
    kern = jnp.asarray(rng.normal(size=k_shape), jnp.float32)
    cand_bco = sorted({min(elastic.round_up(c_o, 128), c)
                       for c in (128, 256, 512)})
    best_bco, best_us = cand_bco[0], float("inf")
    for bco in cand_bco:
        f = jax.jit(lambda x, kern, bco=bco: kraken_conv2d_direct(
            x, kern, stride=stride, bco=bco, interpret=interpret))
        jax.block_until_ready(f(x, kern))
        samples = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x, kern))
            samples.append((time.perf_counter() - t0) * 1e6)
        us = statistics.median(samples)
        if us < best_us:
            best_bco, best_us = bco, us
    cfg = elastic._make_config(m_eq, k_eq, c_o, elastic.SUBLANE,
                               elastic.round_up(k_eq, elastic.MXU_DIM),
                               best_bco, "output_stationary", 4)
    cache.put(key, cfg, measured_us=best_us,
              extra={"candidates_timed": len(cand_bco), "kind": "conv_bco"})
    cache.save()
    if log is not None:
        log(f"[autotune] {key}: bco={best_bco} {best_us:.0f}us")
    return best_bco
