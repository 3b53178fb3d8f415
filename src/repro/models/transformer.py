"""Layer-stack assembly: heterogeneous blocks scanned over repeating periods.

Architectures are described as a repeating *pattern* of slots (e.g. gemma3 =
5 local-attention slots + 1 global slot; llama4 = dense slot + MoE slot;
zamba2 = N mamba slots followed by one invocation of a weight-shared
attention block).  Parameters of each slot are stacked over periods and the
stack is evaluated with ``lax.scan`` so the compiled HLO contains each
distinct block body once — essential to keep 48-layer x 512-device AOT
compiles tractable, and the direct analogue of Kraken processing every layer
through one fixed engine configuration.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import KVCache, Spec

Params = dict


@dataclasses.dataclass(frozen=True)
class Slot:
    kind: str          # 'attn' | 'cross' | 'rwkv' | 'mamba'
    ffn: str           # 'mlp' | 'moe' | 'cmix' | 'none'
    window: int = 0    # sliding window for 'attn' (0 = full)


def build_pattern(cfg) -> tuple[list[Slot], bool]:
    """Return (pattern, has_shared_attn)."""
    fam = cfg.family
    if fam == "ssm":
        return [Slot("rwkv", "cmix")], False
    if fam == "hybrid":
        return [Slot("mamba", "none")] * cfg.mamba_per_shared_attn, True
    if fam == "vlm" and cfg.cross_attn_period:
        p = [Slot("attn", "mlp")] * (cfg.cross_attn_period - 1)
        return p + [Slot("cross", "mlp")], False
    if cfg.local_global_period:
        p = [Slot("attn", "mlp", window=cfg.local_window)] * (cfg.local_global_period - 1)
        return p + [Slot("attn", "mlp", window=0)], False
    ffn_all = "moe" if (cfg.num_experts and cfg.moe_interleave == 1) else "mlp"
    if cfg.num_experts and cfg.moe_interleave > 1:
        p = [Slot("attn", "mlp", window=cfg.sliding_window)] * (cfg.moe_interleave - 1)
        return p + [Slot("attn", "moe", window=cfg.sliding_window)], False
    return [Slot("attn", ffn_all, window=cfg.sliding_window)], False


# ---------------------------------------------------------------------------
# Per-slot parameter specs
# ---------------------------------------------------------------------------

def slot_specs(cfg, slot: Slot) -> dict[str, Spec]:
    s: dict[str, Spec] = {}
    if slot.kind in ("attn", "cross"):
        s.update(L.norm_specs(cfg, "attn_norm"))
        s.update(L.attention_specs(cfg, "attn"))
        if slot.kind == "cross":
            s.update(L.norm_specs(cfg, "cross_kv_norm"))
    elif slot.kind == "rwkv":
        s.update(L.norm_specs(cfg, "attn_norm"))
        s.update(SSM.rwkv_specs(cfg, "rwkv"))
    elif slot.kind == "mamba":
        s.update(L.norm_specs(cfg, "attn_norm"))
        s.update(SSM.mamba_specs(cfg, "mamba"))
    if slot.ffn == "mlp":
        s.update(L.norm_specs(cfg, "mlp_norm"))
        s.update(L.mlp_specs(cfg, "mlp"))
    elif slot.ffn == "moe":
        s.update(L.norm_specs(cfg, "mlp_norm"))
        s.update(MOE.moe_specs(cfg, "moe"))
    elif slot.ffn == "cmix":
        s.update(L.norm_specs(cfg, "mlp_norm"))
        s.update(SSM.rwkv_channel_specs(cfg, "cmix"))
    return s


def shared_attn_specs(cfg) -> dict[str, Spec]:
    """zamba2's weight-shared attention+MLP block."""
    s = {}
    s.update(L.norm_specs(cfg, "shared_attn_norm"))
    s.update(L.attention_specs(cfg, "shared_attn"))
    s.update(L.norm_specs(cfg, "shared_mlp_norm"))
    s.update(L.mlp_specs(cfg, "shared_mlp"))
    return s


# ---------------------------------------------------------------------------
# Per-slot caches (decode)
# ---------------------------------------------------------------------------

def slot_cache(cfg, slot: Slot, batch: int, cache_len: int, dtype, *,
               abstract: bool, n_frontend: int = 0,
               clamp_window: bool = True):
    """``clamp_window=False``: keep sliding-window layers at the full
    ``cache_len`` (the serving engine's bucketed prefill writes position-
    identity rows and windows via the mask alone).  Every KV cache carries
    per-slot positions (``pos [B, S_cache]``) — the one decode-state
    layout, shared by lockstep and continuous-batching callers alike."""
    mk = (lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)) if abstract else \
         (lambda shape, dt: jnp.zeros(shape, dt))
    if slot.kind == "attn":
        s_cache = (min(slot.window, cache_len)
                   if (slot.window and clamp_window) else cache_len)
        return (KVCache.specs if abstract else KVCache.init)(
            cfg, batch, s_cache, dtype)
    if slot.kind == "cross":
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": mk((batch, kvh, n_frontend, hd), dtype),
                "v": mk((batch, kvh, n_frontend, hd), dtype)}
    if slot.kind == "rwkv":
        st = (SSM.rwkv_state_specs if abstract else SSM.rwkv_state_init)(cfg, batch, dtype)
        return {"rwkv": st, "cmix_x_prev": mk((batch, cfg.d_model), dtype)}
    if slot.kind == "mamba":
        return (SSM.mamba_state_specs if abstract else SSM.mamba_state_init)(cfg, batch, dtype)
    raise ValueError(slot.kind)


# ---------------------------------------------------------------------------
# Slot application
# ---------------------------------------------------------------------------

class Ctx(NamedTuple):
    mode: str                      # 'train' | 'prefill' | 'decode'
    positions: jax.Array           # [S] shared or [B, S] per-slot positions
    frontend: jax.Array | None     # image/audio embeddings [B, P, d]
    shared_params: Params | None   # zamba2 shared block
    lengths: jax.Array | None = None   # [B] true row lengths (bucketed
                                   # prefill: recurrent layers mask the pads
                                   # out of their carried state)


def _sp(x):
    """Residual-stream constraint: sequence parallel over the model axis
    (Megatron-SP).  Under rules without ``act_seq`` (or indivisible S, e.g.
    decode S=1) this replicates — a no-op."""
    return sharding.shard(x, "batch", "act_seq", "embed")


def _gather_seq(h):
    """Explicit SP boundary: re-gather the sequence dim before a TP block
    (the all-gather half of the Megatron-SP collective pair; the matching
    reduce-scatter is GSPMD's lowering of the block output's pending psum
    onto the seq-sharded residual constraint)."""
    return sharding.shard(h, "batch", "seq", "embed")


def _residual(x, y):
    return _sp(x + y)


def apply_slot(cfg, slot: Slot, params: Params, x: jax.Array, cache,
               ctx: Ctx):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    pos = ctx.positions
    x = _sp(x)
    if slot.kind == "attn":
        h = _gather_seq(L.apply_norm(cfg, params, "attn_norm", x))
        out = L.attention(cfg, params, "attn", h, positions=pos,
                          window=slot.window, cache=cache,
                          lengths=ctx.lengths)
        x = _residual(x, out.y)
        new_cache = out.cache
    elif slot.kind == "cross":
        h = _gather_seq(L.apply_norm(cfg, params, "attn_norm", x))
        if ctx.mode == "decode" or (ctx.frontend is None
                                    and cache is not None):
            # kv computed at prefill and frozen in the cache.  Text-only
            # serving never supplies a frontend: attend over the cached KV
            # as-is (all-zero KV attends to nothing useful and contributes
            # a zero residual) — identical between the sequential oracle
            # and the engine's bucketed prefill.
            out_y = _cross_from_cache(cfg, params, h, cache, pos)
            x = _residual(x, out_y)
            new_cache = cache
        else:
            kv_src = L.apply_norm(cfg, params, "cross_kv_norm", ctx.frontend)
            out = L.attention(cfg, params, "attn", h, positions=pos,
                              kv_x=kv_src, causal=False)
            x = _residual(x, out.y)
            new_cache = _project_cross_kv(cfg, params, kv_src) if cache is not None else None
    elif slot.kind == "rwkv":
        h = _gather_seq(L.apply_norm(cfg, params, "attn_norm", x))
        st = cache["rwkv"] if cache is not None else None
        if ctx.mode == "decode":
            y, st_new = SSM.rwkv_step(cfg, params, "rwkv", h, st,
                                      lengths=ctx.lengths)
        else:
            y, st_new = SSM.rwkv_mix(cfg, params, "rwkv", h, st,
                                     lengths=ctx.lengths)
        x = _residual(x, y)
        new_cache = dict(cache, rwkv=st_new) if cache is not None else None
    elif slot.kind == "mamba":
        h = _gather_seq(L.apply_norm(cfg, params, "attn_norm", x))
        if ctx.mode == "decode":
            y, st_new = SSM.mamba_step(cfg, params, "mamba", h, cache,
                                       lengths=ctx.lengths)
        else:
            y, st_new = SSM.mamba_mix(cfg, params, "mamba", h, cache,
                                      lengths=ctx.lengths)
        x = _residual(x, y)
        new_cache = st_new
    else:
        raise ValueError(slot.kind)

    if slot.ffn == "mlp":
        h = _gather_seq(L.apply_norm(cfg, params, "mlp_norm", x))
        x = _residual(x, L.mlp(cfg, params, "mlp", h))
    elif slot.ffn == "moe":
        h = _gather_seq(L.apply_norm(cfg, params, "mlp_norm", x))
        out = MOE.moe_block(cfg, params, "moe", h)
        x = _residual(x, out.y)
        aux = aux + out.aux_loss
    elif slot.ffn == "cmix":
        h = _gather_seq(L.apply_norm(cfg, params, "mlp_norm", x))
        xp = cache["cmix_x_prev"] if cache is not None else jnp.zeros(
            (x.shape[0], cfg.d_model), x.dtype)
        y, xp_new = SSM.rwkv_channel_mix(cfg, params, "cmix", h, xp,
                                         lengths=ctx.lengths)
        x = _residual(x, y)
        if new_cache is not None:
            new_cache = dict(new_cache, cmix_x_prev=xp_new)
    return x, new_cache, aux


def _project_cross_kv(cfg, params: Params, kv_src: jax.Array):
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = L.dense(kv_src, params["attn_wk"], bias=params.get("attn_bk"))
    v = L.dense(kv_src, params["attn_wv"], bias=params.get("attn_bv"))
    reshape = lambda t: t.reshape(t.shape[0], t.shape[1], kv, hd).transpose(0, 2, 1, 3)
    return {"k": reshape(k), "v": reshape(v)}


def _cross_from_cache(cfg, params: Params, h: jax.Array, cache, pos):
    hds, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.dense(h, params["attn_wq"], bias=params.get("attn_bq"))
    b, sq, _ = h.shape
    qh = q.reshape(b, sq, hds, hd).transpose(0, 2, 1, 3)
    out = L._gqa_sdpa(qh, cache["k"], cache["v"], mask_mode="none", window=0,
                      q_pos=pos, kv_pos=jnp.arange(cache["k"].shape[2]))
    y = L.dense(L._merge_heads(out), params["attn_wo"])
    return y


def apply_shared_attn(cfg, params: Params, x: jax.Array, cache, ctx: Ctx):
    x = _sp(x)
    h = _gather_seq(L.apply_norm(cfg, params, "shared_attn_norm", x))
    out = L.attention(cfg, params, "shared_attn", h, positions=ctx.positions,
                      cache=cache, lengths=ctx.lengths)
    x = _residual(x, out.y)
    h = _gather_seq(L.apply_norm(cfg, params, "shared_mlp_norm", x))
    x = _residual(x, L.mlp(cfg, params, "shared_mlp", h))
    return x, out.cache


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

class LayerStack:
    def __init__(self, cfg):
        self.cfg = cfg
        self.pattern, self.has_shared = build_pattern(cfg)
        p = len(self.pattern)
        self.n_periods = cfg.num_layers // p
        self.n_tail = cfg.num_layers % p

    # ---- specs -------------------------------------------------------------
    def param_specs_dict(self) -> dict[str, Any]:
        cfg = self.cfg
        out: dict[str, Any] = {"slots": [], "tail": []}
        for slot in self.pattern:
            specs = slot_specs(cfg, slot)
            out["slots"].append({
                k: Spec((self.n_periods,) + s.shape, ("layers",) + s.axes, s.scale)
                for k, s in specs.items()})
        for i in range(self.n_tail):
            out["tail"].append(slot_specs(cfg, self.pattern[i]))
        if self.has_shared:
            out["shared"] = shared_attn_specs(cfg)
        return out

    # ---- caches -------------------------------------------------------------
    def cache_tree(self, batch: int, cache_len: int, dtype, *, abstract: bool,
                   n_frontend: int = 0, flat: bool = False,
                   clamp_window: bool = True):
        """``flat=False``: per-slot caches stacked over periods (the scan
        layout).  ``flat=True``: one separate buffer per layer (the serving
        layout — each layer's persistent KV buffer aliases in place under
        donation instead of being threaded through a scan carry).
        §Perf cell-3 iteration 3.  ``clamp_window`` is the bucketed-prefill
        knob, see :func:`slot_cache`."""
        cfg = self.cfg
        def one(slot):
            return slot_cache(cfg, slot, batch, cache_len, dtype,
                              abstract=abstract, n_frontend=n_frontend,
                              clamp_window=clamp_window)
        def stacked(slot):
            c = one(slot)
            def add_dim(leaf):
                if abstract:
                    return jax.ShapeDtypeStruct((self.n_periods,) + leaf.shape, leaf.dtype)
                return jnp.broadcast_to(leaf, (self.n_periods,) + leaf.shape).copy() \
                    if hasattr(leaf, "shape") else leaf
            return jax.tree.map(add_dim, c)
        if flat:
            tree = {"slots": [[one(s) for _ in range(self.n_periods)]
                              for s in self.pattern],
                    "tail": [one(self.pattern[i]) for i in range(self.n_tail)]}
            if self.has_shared:
                sh = Slot("attn", "none")
                tree["shared"] = [slot_cache(cfg, sh, batch, cache_len, dtype,
                                             abstract=abstract,
                                             clamp_window=clamp_window)
                                  for _ in range(self.n_periods)]
            return tree
        tree = {"slots": [stacked(s) for s in self.pattern],
                "tail": [one(self.pattern[i]) for i in range(self.n_tail)]}
        if self.has_shared:
            sh = Slot("attn", "none")
            c = slot_cache(cfg, sh, batch, cache_len, dtype, abstract=abstract,
                           clamp_window=clamp_window)
            def add_dim(leaf):
                if abstract:
                    return jax.ShapeDtypeStruct((self.n_periods,) + leaf.shape, leaf.dtype)
                return jnp.broadcast_to(leaf, (self.n_periods,) + leaf.shape).copy()
            tree["shared"] = jax.tree.map(add_dim, c)
        return tree

    @staticmethod
    def caches_are_flat(caches) -> bool:
        return bool(caches) and isinstance(caches.get("slots", [None])[0], list)

    def stack_caches(self, flat_tree):
        """Flat per-layer layout -> stacked scan layout (one concat/slot)."""
        out = {"slots": [jax.tree.map(lambda *xs: jnp.stack(xs),
                                      *flat_tree["slots"][s])
                         for s in range(len(self.pattern))],
               "tail": list(flat_tree.get("tail", []))}
        if self.has_shared:
            out["shared"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                         *flat_tree["shared"])
        return out

    def unstack_caches(self, caches):
        """Stacked scan layout -> flat per-layer layout (slicing views)."""
        out = {"slots": [[jax.tree.map(lambda a: a[i], caches["slots"][s])
                          for i in range(self.n_periods)]
                         for s in range(len(self.pattern))],
               "tail": list(caches.get("tail", []))}
        if self.has_shared:
            out["shared"] = [jax.tree.map(lambda a: a[i], caches["shared"])
                             for i in range(self.n_periods)]
        return out

    # ---- forward -------------------------------------------------------------
    def apply(self, params: Params, x: jax.Array, ctx: Ctx, caches=None,
              remat: str = "none", unroll: bool = False):
        """Returns (x, new_caches, aux_loss).

        ``unroll=True`` (decode): iterate layers as straight-line code with
        functional ``.at[i].set`` updates into the stacked cache instead of
        ``lax.scan``.  With the cache argument donated, XLA aliases the
        buffer and every layer's update is a true in-place slice write —
        the vLLM-style persistent KV buffer.  Scanning instead carries the
        stack through the loop (full-stack slice/update machinery per
        iteration, plus a f32 normalization twin of the whole cache on
        CPU hosts) — §Perf cell-3 iteration 2.  Train/prefill keep the
        scan: the compiled HLO holds each distinct block body once, which
        is what keeps 512-device AOT compiles tractable.
        """
        cfg = self.cfg
        use_cache = caches is not None
        if unroll:
            return self._apply_unrolled(params, x, ctx, caches)
        was_flat = use_cache and self.caches_are_flat(caches)
        if was_flat:  # scan needs the stacked layout; convert in/out
            caches = self.stack_caches(caches)

        def period_body(carry, xs):
            x, aux = carry
            slot_params, slot_caches, shared_cache = xs
            new_slot_caches = []
            for i, slot in enumerate(self.pattern):
                c = slot_caches[i] if use_cache else None
                x, c_new, a = apply_slot(cfg, slot, slot_params[i], x, c, ctx)
                new_slot_caches.append(c_new if use_cache else 0)
                aux = aux + a
            new_shared = 0
            if self.has_shared:
                x, new_shared = apply_shared_attn(
                    cfg, params["shared"], x, shared_cache if use_cache else None, ctx)
                if not use_cache:
                    new_shared = 0
            return (x, aux), (new_slot_caches, new_shared)

        body = period_body
        if remat == "full":
            body = jax.checkpoint(period_body)
        elif remat == "dots":
            body = jax.checkpoint(
                period_body,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        xs_params = [params["slots"][i] for i in range(len(self.pattern))]
        dummy = jnp.zeros((self.n_periods,), jnp.int8)
        xs_caches = ([caches["slots"][i] for i in range(len(self.pattern))]
                     if use_cache else [dummy] * len(self.pattern))
        xs_shared = caches.get("shared", dummy) if use_cache else dummy
        (x, aux), (ys_caches, ys_shared) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (xs_params, xs_caches, xs_shared))

        new_caches = None
        if use_cache:
            new_caches = {"slots": list(ys_caches), "tail": [],
                          **({"shared": ys_shared} if self.has_shared else {})}
        # tail layers (pattern remainder), unrolled
        for i in range(self.n_tail):
            c = caches["tail"][i] if use_cache else None
            x, c_new, a = apply_slot(cfg, self.pattern[i], params["tail"][i],
                                     x, c, ctx)
            aux = aux + a
            if use_cache:
                new_caches["tail"].append(c_new)
        if use_cache and was_flat:
            new_caches = self.unstack_caches(new_caches)
        return x, new_caches, aux

    def _apply_unrolled(self, params: Params, x: jax.Array, ctx: Ctx, caches):
        """Straight-line layer loop; in-place cache updates.

        Flat cache layout (serving): each layer's buffer is a separate tree
        leaf, replaced wholesale — under donation XLA aliases every one of
        them, so a decode step's cache traffic is slot-sized.  Stacked
        layout falls back to functional ``.at[i].set`` updates.
        """
        cfg = self.cfg
        use_cache = caches is not None
        flat = use_cache and self.caches_are_flat(caches)
        aux = jnp.zeros((), jnp.float32)
        new_caches = None
        if use_cache:
            new_caches = dict(caches)
            new_caches["slots"] = [list(sl) if flat else sl
                                   for sl in new_caches["slots"]]
            new_caches["tail"] = list(new_caches.get("tail", []))
            if self.has_shared and flat:
                new_caches["shared"] = list(new_caches["shared"])

        def get(slot_entry, i):
            if not use_cache:
                return None
            return slot_entry[i] if flat else jax.tree.map(
                lambda a: a[i], slot_entry)

        for i in range(self.n_periods):
            for s, slot in enumerate(self.pattern):
                # slice layer i's weights only once x exists: a slice that
                # feeds a Pallas kernel is a copy, and unordered XLA hoists
                # every layer's copy to the program start (yi-6b decode:
                # 5.45 GB of temps, past a 16 GB chip's HBM)
                x, stacked = jax.lax.optimization_barrier(
                    (x, params["slots"][s]))
                sp = jax.tree.map(lambda a: a[i], stacked)
                c = get(new_caches["slots"][s], i) if use_cache else None
                x, c_new, a = apply_slot(cfg, slot, sp, x, c, ctx)
                aux = aux + a
                if use_cache and c_new is not None:
                    if flat:
                        new_caches["slots"][s][i] = c_new
                    else:
                        new_caches["slots"][s] = jax.tree.map(
                            lambda st, nw: st.at[i].set(nw),
                            new_caches["slots"][s], c_new)
            if self.has_shared:
                sc = get(new_caches["shared"], i) if use_cache else None
                x, sh_new = apply_shared_attn(cfg, params["shared"], x, sc, ctx)
                if use_cache and sh_new is not None:
                    if flat:
                        new_caches["shared"][i] = sh_new
                    else:
                        new_caches["shared"] = jax.tree.map(
                            lambda st, nw: st.at[i].set(nw),
                            new_caches["shared"], sh_new)
        for i in range(self.n_tail):
            c = caches["tail"][i] if use_cache else None
            x, c_new, a = apply_slot(cfg, self.pattern[i], params["tail"][i],
                                     x, c, ctx)
            aux = aux + a
            if use_cache:
                new_caches["tail"][i:i + 1] = [c_new]
        return x, new_caches, aux
