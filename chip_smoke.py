#!/usr/bin/env python3
"""Chip smoke: the serving engine's main path, end to end, on one TPU.

    python chip_smoke.py                # one chip: kernels, then serving
    python chip_smoke.py --four-chips   # the trainer on a 2x2 mesh vs one chip

One chip.  The Pallas GEMM and the fused paged-decode kernel are compared
with a float32 reference at yi-6b widths.  Then yi-6b at its published
widths (32 layers, d_model 4096, 32 heads / 4 KV heads, d_ff 11008, vocab
64000, bf16, random weights from a seed) serves 8 requests twice through
``PagedEngine`` (4 slots, 16-token pages, 2048-token cache, 256-token
chunks; prompts of 200-1500 tokens, 32 new tokens each).  Both engine
programs must hold the Pallas kernels, every request must end DONE with
in-vocab tokens, the warm pass must retrace nothing, and both passes must
emit the same tokens.

Four chips.  The trainer (``repro.launch.train.Trainer``) runs yi-6b at
published widths cut to 2 layers for 3 AdamW steps on one chip, then on a
2x2 (data, model) mesh, from the same seed and batches.  Losses and
gradient norms must agree, the compiled mesh step must hold collectives,
and no device may hold the whole parameter set.

Every line but the last is a smoke reading, not a benchmark.  The last
line is one JSON object naming the device.  Without a TPU the script exits
non-zero and prints no result; it never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
SLOTS, PAGE_SIZE, MAX_LEN, CHUNK = 4, 16, 2048, 256
PROMPT_LENS = [200 + 1300 * i // 7 for i in range(8)]     # 200 .. 1500
MAX_NEW = 32
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 4, 512, 3
KERNEL = "tpu_custom_call"      # what a Pallas kernel lowers to on a TPU

# The Pallas GEMM takes bf16 operands, accumulates in f32 and rounds its
# output to bf16 (8 significant bits: half an ulp is 2^-9 of the value).
# The reference is the f32 product of the same bf16 operands, so anything
# past a few bf16 ulps of the largest output is a kernel fault.
GEMM_TOL = 2.0 ** -7
# Paged decode, error over max|V|: the bf16 kernel rounds the softmax
# weights to bf16 before P.V (error <= 2^-9 * max|V|) and its output to
# bf16 (<= 2^-9 * max|V|, since the output is a convex mix of V rows);
# the int8 kernel dequantizes exactly and rounds only its output.
PAGED_TOL = 2.0 ** -7
# Trainer, one chip vs 2x2 mesh: one side runs the Pallas GEMM (and its
# custom VJP), the other XLA's dot, both bf16 in / f32 accumulate, summed
# in different orders.  Rounding noise in bf16 activations (2^-9 relative)
# averages over 2048 tokens far below LOSS_TOL; a missing or doubled
# gradient reduction moves the gradient norm by tens of percent.
LOSS_TOL = 2e-2                 # absolute, on a loss near ln(64000) = 11.07
GNORM_RTOL = 2e-2


def note(msg: str) -> None:
    print(f"[smoke reading, not a benchmark] {msg}", flush=True)


def check(ok, what) -> None:
    """A failed phase ends the run non-zero (unlike ``assert``, also under
    ``python -O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def published_config():
    """yi-6b from the registry, checked against its published widths."""
    from repro.configs import get_arch
    cfg = get_arch("yi-6b")
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size, cfg.dtype)
    check(got == (32, 4096, 32, 4, 11008, 64000, "bfloat16"), got)
    return cfg


def compiled_with_kernel(fn, *args):
    """Compile ``fn`` for ``args``; check a Pallas kernel is in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    check(KERNEL in compiled.as_text(), f"no {KERNEL} in {fn}")
    return compiled


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def check_gemm(cfg, key) -> None:
    """``kraken_matmul`` at the engine's decode and mixed M, for the q,
    up, down and unembed projections, against an f32 reference."""
    from repro.kernels import ops
    d = cfg.d_model
    shapes = {"q": (d, cfg.num_heads * cfg.head_dim), "up": (d, cfg.d_ff),
              "down": (cfg.d_ff, d), "unembed": (d, cfg.vocab_size)}
    gemm = lambda a, b: ops.kraken_matmul(a, b, use_pallas=True)
    for m in (SLOTS, SLOTS * CHUNK):
        for name, (k, n) in shapes.items():
            ka, kb, key = jax.random.split(key, 3)
            a = jax.random.normal(ka, (m, k), jnp.bfloat16)
            b = (jax.random.normal(kb, (k, n), jnp.float32)
                 / math.sqrt(k)).astype(jnp.bfloat16)
            got = compiled_with_kernel(gemm, a, b)(a, b).astype(jnp.float32)
            want = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            note(f"kraken_matmul {name} M={m} K={k} N={n}: "
                 f"max err / max|ref| = {err:.3e} (limit {GEMM_TOL:.3e})")
            check(err <= GEMM_TOL, (name, m, err))


def check_paged_decode(cfg, key, *, quantized: bool) -> None:
    """The fused paged-decode kernel on a shuffled pool at the engine's
    geometry (full, partial, short and sub-page slots) against an f32
    reference."""
    from repro.kernels import ops, ref
    from repro.kernels.decode_attention import quantize_kv
    from repro.models.layers import POS_EMPTY
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    mp = MAX_LEN // PAGE_SIZE
    n_pages = SLOTS * mp + 1                  # one spare page, never mapped
    rng = np.random.default_rng(SEED)
    table = rng.permutation(n_pages)[:SLOTS * mp].reshape(SLOTS, mp)
    lengths = [MAX_LEN, MAX_LEN * 3 // 4, MAX_LEN // 7, PAGE_SIZE // 2 + 1]
    pos = np.full((n_pages, PAGE_SIZE), POS_EMPTY, np.int64)
    for b, ln in enumerate(lengths):
        for j in range(mp):
            p = j * PAGE_SIZE + np.arange(PAGE_SIZE)
            pos[table[b, j]] = np.where(p < ln, p, POS_EMPTY)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (SLOTS, cfg.num_heads, d), jnp.bfloat16)
    k = jax.random.normal(kk, (n_pages, kvh, PAGE_SIZE, d), jnp.float32)
    v = jax.random.normal(kv, (n_pages, kvh, PAGE_SIZE, d), jnp.float32)
    if quantized:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        v_max = jnp.max(jnp.abs(v.astype(jnp.float32) * vs[..., None]))
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ks = vs = None
        v_max = jnp.max(jnp.abs(v.astype(jnp.float32)))
    args = (q, k, v, jnp.asarray(pos, jnp.int32),
            jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32) - 1, ks, vs)

    def fused(q, k, v, pos, tbl, qp, ks, vs):
        return ops.kraken_paged_attention(
            q, k, v, pos_pages=pos, page_table=tbl, q_pos=qp, k_scale=ks,
            v_scale=vs, use_pallas=True)

    def reference(q, k, v, pos, tbl, qp, ks, vs):
        return ref.paged_decode_attention(
            q.astype(jnp.float32), k, v, pos_pages=pos, page_table=tbl,
            q_pos=qp, k_scale=ks, v_scale=vs)

    got = compiled_with_kernel(fused, *args)(*args).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(*args)
    err = float(jnp.max(jnp.abs(got - want)) / v_max)
    note(f"paged_decode_attention {'int8' if quantized else 'bf16'} pool "
         f"(slots={SLOTS} pages={n_pages} page_size={PAGE_SIZE} "
         f"len={MAX_LEN}): max err / max|V| = {err:.3e} "
         f"(limit {PAGED_TOL:.3e})")
    check(err <= PAGED_TOL, (quantized, err))


def serve(cfg, params) -> None:
    """Serve the workload twice through one engine; check every request,
    the kernels in both programs, and a warm pass with no retrace."""
    from repro.models.model import Model
    from repro.serving import DONE, CacheConfig, EngineConfig, PagedEngine
    config = EngineConfig(slots=SLOTS, chunk=CHUNK, seed=SEED,
                          decode_kernel="fused",
                          cache=CacheConfig(page_size=PAGE_SIZE,
                                            max_len=MAX_LEN))
    eng = PagedEngine(Model(cfg), params, config=config)
    check(eng.decode_kernel == "fused", eng.decode_kernel)

    # compile both token programs before the first request: the compile is
    # set-up, and its HLO shows the kernels (GEMM in both, paged decode in
    # the decode program) rather than a reference path
    zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
    programs = {
        "mixed": (eng._prefill, (params, eng.pools, zeros(SLOTS, eng.chunk),
                                 zeros(SLOTS, eng.chunk), zeros(SLOTS))),
        "decode": (eng._decode, (params, eng.pools, zeros(SLOTS, 1),
                                 zeros(SLOTS), zeros(SLOTS))),
    }
    for name, (program, args) in programs.items():
        t0 = time.perf_counter()
        text = program.lower(*args).compile().as_text()
        note(f"compile {name} program: {time.perf_counter() - t0:.1f} s, "
             f"{text.count(KERNEL)} Pallas kernel calls")
        check(KERNEL in text, f"no {KERNEL} in the {name} program")

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in PROMPT_LENS]
    outs = []
    for p in (1, 2):
        counters = (eng._prefill, eng._decode)
        before = [(c.retraces, c.cache_size) for c in counters]
        steps = eng.steps
        t0 = time.perf_counter()
        reqs = [eng.submit(prompt, MAX_NEW) for prompt in prompts]
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        for r in reqs:
            check(r.state == DONE, (r.rid, r.state, r.error))
            check(len(r.out) == MAX_NEW, (r.rid, len(r.out)))
            check(all(0 <= t < cfg.vocab_size for t in r.out), r.rid)
        outs.append([list(r.out) for r in reqs])
        new = [(c.retraces - r0, c.cache_size - s0)
               for c, (r0, s0) in zip(counters, before)]
        n_steps = eng.steps - steps
        note(f"pass {p}: {len(reqs)} requests DONE, {n_steps} engine steps "
             f"in {wall:.2f} s ({wall / n_steps * 1e3:.1f} ms/step, host "
             f"clock); new signatures mixed/decode = {new[0][0]}/{new[1][0]}")
        if p == 2:
            check(new == [(0, 0), (0, 0)], f"warm retraces {new}")
    check(outs[0] == outs[1], "the warm pass emitted different tokens")
    note(f"served {2 * len(prompts)} requests; warm pass token-identical")


def one_chip() -> None:
    from repro import tuning
    from repro.models.model import Model
    # static tile plans, no tile cache: what runs is the checked-in code
    tuning.set_tile_mode("model")
    cfg = published_config()
    key = jax.random.key(SEED)
    check_gemm(cfg, jax.random.fold_in(key, 1))
    for quantized in (False, True):
        check_paged_decode(cfg, jax.random.fold_in(key, 2),
                           quantized=quantized)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(Model(cfg).init)(key))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    note(f"yi-6b weights: {n_bytes / 1e9:.2f} GB made on the chip in "
         f"{time.perf_counter() - t0:.1f} s")
    serve(cfg, params)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def bytes_per_device(tree) -> dict:
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def train_run(trainer, batches):
    """TRAIN_STEPS steps from the seed: (losses, grad norms, compiled step
    text, compile seconds, state bytes and parameter bytes per device)."""
    params, ostate = trainer.init(jax.random.key(SEED))
    per_dev = bytes_per_device((params, ostate))
    param_dev = bytes_per_device(params)
    t0 = time.perf_counter()
    text = trainer.lower(params, ostate, batches[0]).compile().as_text()
    compile_s = time.perf_counter() - t0
    losses, gnorms = [], []
    for batch in batches:
        params, ostate, metrics = trainer.step(params, ostate, batch)
        losses.append(float(metrics["loss"]))
        gnorms.append(float(metrics["grad_norm"]))
    del params, ostate
    return losses, gnorms, text, compile_s, per_dev, param_dev


def four_chips() -> None:
    from repro import sharding as Sh
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import Trainer
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    check(len(jax.devices()) == 4, jax.devices())
    cfg = dataclasses.replace(published_config(), num_layers=TRAIN_LAYERS)
    model = Model(cfg)
    # a constant rate at the trainer's peak: steps 2-3 then see updates
    # large enough that a wrong gradient reduction would show in the loss
    opt = AdamW(lr=3e-4)
    total = sum(math.prod(s.shape) * s.dtype.itemsize
                for s in jax.tree.leaves(model.param_specs()))
    pipe = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=SEED)
    batches = [{k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
               for i in range(TRAIN_STEPS)]

    one = train_run(Trainer(model, opt), batches)
    note(f"1 chip: step compile {one[3]:.1f} s, losses {one[0]}, "
         f"grad norms {one[1]}, state {max(one[4].values()) / 1e9:.2f} GB")
    mesh = make_host_mesh(2, 2)
    four = train_run(Trainer(model, opt, mesh=mesh,
                             rules=dict(Sh.RULES_SINGLE_POD)), batches)
    colls = [c for c in ("all-reduce", "all-gather", "reduce-scatter",
                         "collective-permute", "all-to-all") if c in four[2]]
    note(f"2x2 mesh: step compile {four[3]:.1f} s, losses {four[0]}, "
         f"grad norms {four[1]}, collectives {colls}")
    for dev in sorted(four[4], key=lambda d: d.id):
        note(f"2x2 mesh: device {dev.id} holds {four[5][dev] / 1e9:.3f} GB "
             f"of {total / 1e9:.3f} GB parameters, {four[4][dev] / 1e9:.3f} "
             f"GB of parameters + AdamW state")
    check(colls, "the compiled mesh step has no collective")
    check(max(four[5].values()) < total, "a device holds every parameter")
    for l1, l4, g1, g4 in zip(one[0], four[0], one[1], four[1]):
        check(abs(l1 - l4) <= LOSS_TOL, (one[0], four[0]))
        check(abs(g1 - g4) <= GNORM_RTOL * g1, (one[1], four[1]))
    note(f"losses agree within {LOSS_TOL}, grad norms within "
         f"{GNORM_RTOL:.0%}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the trainer's 2x2 mesh against one chip")
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import enable_compile_cache
    note(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    note(f"peak device memory: "
         f"{dev.memory_stats()['peak_bytes_in_use'] / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
