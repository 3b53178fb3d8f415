"""Serving launcher: a thin frontend over the serving engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --requests 8 --max-new 16 --prompt-lens 5,9,12 --chunk 8

The one path is :class:`repro.serving.engine.PagedEngine` — the uniform
LayerState tree (paged KV pools for attention layers, slot-row states for
RWKV/Mamba/cross-attn), chunked-prefill continuous batching (prompts
stream in ``--chunk`` tokens per mixed step, fused with every live decode
slot under ``--step-budget`` — decode never stalls behind a long prompt,
and a warm engine never retraces), priority admission with aging +
per-request metrics.  ``--priority 0,1`` cycles priority classes over
requests, ``--preempt`` lets an urgent arrival swap a lower-class victim
out to host (and back, token-identically — ``--verify-preempt`` replays
the workload through a preempt-off engine and asserts identity),
``--stagger N`` runs N engine steps between submissions so later arrivals
meet a busy engine, and ``--slo-ttft-ms``/``--slo-e2e-ms`` set the
per-class SLO targets the report's attainment lines are scored against.
Every architecture in the registry serves through it: ``--arch rwkv6-3b``
and ``--arch zamba2-1.2b`` run the same programs as ``--arch yi-6b``.
``--repeat 2`` serves the workload twice through one engine and prints the
second pass's compile deltas (the CI smokes assert
``prefill retraces=0 decode retraces=0`` and ``max decode stall=0``).

Fault tolerance (DESIGN.md §14): ``--deadline-s`` gives every request a
wall-clock budget (TIMEOUT past it), ``--faults SPEC`` injects a seeded
deterministic fault plan (step exceptions recover through the PREEMPTED
retry path — ``--verify-faults`` asserts every surviving request is
token-identical to a fault-free replay), ``--watchdog`` runs periodic +
at-drain invariant sweeps, and ``--heartbeat PATH`` writes a liveness
file an external orchestrator can poll.

Speculative decoding (DESIGN.md §15): ``--speculate K`` drafts up to K
tokens per decoding slot from the request's own committed history (n-gram
prompt lookup — no second model) and verifies them inside the very same
mixed chunk program (the engine still compiles exactly three programs);
rejected drafts roll back via ``LayerState.truncate``.  Greedy only.
``--verify-speculate`` replays the workload through a speculation-off
engine and asserts token identity.

The legacy dense-cache continuous-batching loop (and its ``--dense``
escape hatch) was deleted; its sequential per-request form survives only
as the equivalence oracle in ``tests/test_serving_engine.py``.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.launch.engine_args import add_engine_args, engine_config_from_args
from repro.models.model import Model
from repro.runtime.compile_cache import enable_compile_cache


def warm_tile_cache(cfg, *, slots: int, prompt_lens: list[int],
                    cache_len: int, autotune: bool, prefill_batch: int = 1,
                    paged_geoms: list[tuple[int, int, int, int]] | None = None,
                    page_size: int = 8, log=print) -> None:
    """Warm (or verify) the tile-plan cache for this server's GEMM cells.

    Enumerates the prefill cells of every prompt bucket plus the batched
    decode cells (attention projections *and* the RWKV/Mamba projection
    GEMMs of the recurrent families — the work-list follows
    ``core.unified.arch_cells``), autotunes each cache miss, and reports
    per-cell hit/tuned status — the second run of a warmed server reports
    hits for every cell.  ``paged_geoms`` additionally tunes the fused
    paged-decode kernel's ``pages_per_block`` per pool geometry under
    ``op_kind="paged_decode"`` (empty for attention-free archs), so
    ``--autotune`` warmup covers decode attention too.  After warmup the
    process-wide tile mode is "cached", so the serving hot path replays
    measured winners and never benchmarks.
    """
    from repro import tuning
    from repro.core.unified import serving_cells

    cells = serving_cells(cfg, slots=slots, prompt_len=max(prompt_lens),
                          cache_len=cache_len, prefill_batch=prefill_batch,
                          bucket_lens=sorted(set(prompt_lens)))
    cache = tuning.get_tile_cache()
    if autotune:
        # Key/measure in the model's compute dtype: the hot path looks
        # plans up under the activation dtype's name.
        tuning.warm_cells(cells, cache=cache, dtype_name=cfg.dtype, log=log)
        # Key on the *pool* dtype, which is what the serve-time ppb lookup
        # keys on (k_pages.dtype.name): int8 pools must warm int8 entries,
        # not compute-dtype ones that would never be hit.
        pool_dtype = ("int8" if getattr(cfg, "kv_cache_dtype", "") == "int8"
                      else cfg.dtype)
        for g_slots, logical, head_dim, window in paged_geoms or []:
            key = tuning.cache_key("paged_decode", g_slots, logical, head_dim,
                                   pool_dtype, tuning.backend_name())
            mp = max(1, logical // page_size)
            was_hit = tuning.lookup_paged_decode(
                cache, key, page_size=page_size,
                kv_heads=cfg.num_kv_heads, max_pages=mp,
                count=False) is not None
            ppb = tuning.autotune_paged_decode(
                g_slots, logical, head_dim, page_size=page_size,
                kv_heads=cfg.num_kv_heads, q_heads=cfg.num_heads,
                window=window, dtype_name=pool_dtype, cache=cache, log=log)
            # a cell the interpret-mode cap skipped persists nothing
            tuned = tuning.lookup_paged_decode(
                cache, key, page_size=page_size,
                kv_heads=cfg.num_kv_heads, max_pages=mp,
                count=False) is not None
            status = "hit" if was_hit else "tuned" if tuned else "skipped"
            log(f"tile-cache {status:<7} "
                f"paged_decode       m={g_slots:<6} k={logical:<6} "
                f"n={head_dim:<6} -> pages_per_block={ppb}")
        # MoE archs additionally tune the grouped expert GEMM's block_rows
        # per (token-width, direction) cell: the engine runs exactly two
        # token widths (mixed = slots*chunk, decode = slots) and each MoE
        # block is two GEMM shapes (d->f for gate/up, f->d for down).
        if getattr(cfg, "num_experts", 0):
            from repro.models.moe import expert_capacity
            e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
            for tokens in sorted({slots, slots * max(prompt_lens)}):
                cap = expert_capacity(tokens, cfg)
                m_total = e * cap
                for kk, nn in ((d, f), (f, d)):
                    key = tuning.cache_key("moe_gemm", m_total, kk, nn,
                                           cfg.dtype, tuning.backend_name())
                    was_hit = tuning.lookup_moe_gemm(
                        cache, key, experts=e, rows_per_group=cap,
                        dtype_name=cfg.dtype, count=False) is not None
                    bm = tuning.autotune_moe_gemm(
                        e, m_total, kk, nn, dtype_name=cfg.dtype,
                        cache=cache, log=log)
                    tuned = tuning.lookup_moe_gemm(
                        cache, key, experts=e, rows_per_group=cap,
                        dtype_name=cfg.dtype, count=False) is not None
                    status = ("hit" if was_hit
                              else "tuned" if tuned else "skipped")
                    log(f"tile-cache {status:<7} "
                        f"moe_gemm           m={m_total:<6} k={kk:<6} "
                        f"n={nn:<6} -> block_rows={bm}")
    else:
        log(f"tile-cache: loaded {len(cache)} entries from "
            f"{cache.path or '<memory>'} for {len(cells)} serving cells"
            + (f" + {len(paged_geoms)} paged-decode geoms" if paged_geoms
               else ""))
    tuning.set_tile_mode("cached")


def _parse_lens(spec: str | None, default: int) -> list[int]:
    if not spec:
        return [default]
    return [int(x) for x in spec.split(",") if x.strip()]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-6b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--prompt-lens", default=None, metavar="L1,L2,...",
                   help="mixed prompt lengths, cycled over requests "
                        "(exercises the bucketed prefill)")
    p.add_argument("--max-new", type=int, default=16)
    # Every engine knob (--slots, --cache-len, --chunk, --paged-kernel,
    # --moe-gemm, --speculate, --faults, ...) is declared once in
    # launch.engine_args and shared with benchmarks/serving_bench.py.
    add_engine_args(p)
    p.add_argument("--dense", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--repeat", type=int, default=1,
                   help="serve the workload N times through one engine; a "
                        "warm pass must print zero retraces")
    p.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                   help="prepend one fixed N-token prefix to every prompt "
                        "(the shared-prefix trace the prefix-cache smoke "
                        "greps a nonzero hit rate from)")
    p.add_argument("--priority", default=None, metavar="P1,P2,...",
                   help="priority classes (0 = most urgent), cycled over "
                        "requests (default: all class 0 == FIFO)")
    p.add_argument("--stagger", type=int, default=0, metavar="N",
                   help="run N engine steps between submissions (bursty "
                        "arrivals: later requests meet a busy engine)")
    p.add_argument("--verify-speculate", action="store_true",
                   help="replay every submission through a fresh "
                        "speculation-off engine and assert token identity "
                        "(greedy only)")
    p.add_argument("--verify-preempt", action="store_true",
                   help="replay every submission through a fresh "
                        "preempt-off engine and assert token identity "
                        "(greedy only)")
    p.add_argument("--verify-faults", action="store_true",
                   help="replay every submission through a fresh "
                        "fault-free engine and assert each request that "
                        "completed under faults is token-identical "
                        "(greedy only)")
    p.add_argument("--autotune", action="store_true",
                   help="benchmark tile candidates for this arch's GEMM "
                        "cells and persist the winners before serving")
    p.add_argument("--tile-cache", default=None, metavar="PATH",
                   help="tile-plan cache file (also: $KRAKEN_TILE_CACHE); "
                        "without --autotune, replays it read-only")
    args = p.parse_args(argv)
    if args.dense:
        p.error(
            "--dense was removed: the legacy dense-cache loop is gone and "
            "every architecture (dense/MoE/SWA/RWKV/Mamba/hybrid/VLM) now "
            "serves through the PagedEngine's uniform LayerState tree "
            "(repro.serving.engine; DESIGN.md §10).  Just drop the flag.")
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    model = Model(cfg)
    from repro.serving import PagedEngine

    lens = _parse_lens(args.prompt_lens, args.prompt_len)
    chunk = args.chunk or args.cache_len
    if args.tile_cache or args.autotune:
        from repro import tuning
        tuning.set_tile_cache(args.tile_cache)
        # The engine runs exactly two token-program widths: the mixed step
        # at the chunk width and the pure decode step at width 1 — the
        # chunk width *is* the prefill cell set, whatever prompt lengths
        # arrive.
        warm_tile_cache(cfg, slots=args.slots, prompt_lens=[chunk],
                        cache_len=args.cache_len, autotune=args.autotune,
                        prefill_batch=args.slots,
                        paged_geoms=PagedEngine.pool_geoms(
                            model, slots=args.slots,
                            page_size=args.page_size,
                            max_len=args.cache_len),
                        page_size=args.page_size)

    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)

    shared = rng.integers(0, cfg.vocab_size,
                          size=(args.shared_prefix,)).astype(np.int32)

    def make_prompts():
        return [np.concatenate([
            shared,
            rng.integers(0, cfg.vocab_size,
                         size=(lens[i % len(lens)],)).astype(np.int32)])
                for i in range(args.requests)]

    prios = _parse_lens(args.priority, 0)
    config = engine_config_from_args(args)
    eng = PagedEngine(model, params, config=config)
    print(f"# paged decode kernel: {eng.decode_kernel} "
          + (f"moe gemm={eng.moe_gemm} " if cfg.num_experts else "")
          + f"chunk={eng.chunk} step budget={eng.step_budget}"
          + (f" prefix cache={'on' if eng.prefix_cache is not None else 'off'}"
             if args.prefix_cache else "")
          + (" preempt=on" if args.preempt else "")
          + (f" speculate={eng.speculate}" if args.speculate else "")
          + (" watchdog=on" if args.watchdog else "")
          + (f" faults[{args.faults}]" if args.faults else ""))
    done = {}
    subs = []   # every submission, for the --verify-preempt replay
    for rep in range(max(1, args.repeat)):
        before = (eng._prefill.retraces, eng._decode.retraces)
        for i, prompt in enumerate(make_prompts()):
            prio = prios[i % len(prios)]
            r = eng.submit(prompt, args.max_new, priority=prio)
            subs.append((r.rid, prompt, args.max_new, prio))
            for _ in range(args.stagger):
                eng.step()
        done = eng.run_until_idle()
        dp = eng._prefill.retraces - before[0]
        dd = eng._decode.retraces - before[1]
        print(f"pass {rep + 1}: prefill retraces={dp} "
              f"decode retraces={dd}")
        print(eng.report())
    for rid in sorted(done):
        print(f"req {rid}: {done[rid][:8]}...")
    expected = args.requests * max(1, args.repeat)
    print(f"served {len(done)}/{expected} requests")
    # a failed, timed-out or rejected request fails the run, whatever the
    # verify replays below find about the requests that completed
    status = 1 if len(done) < expected else 0
    if args.verify_speculate:
        # replay the exact submissions through a fresh engine with
        # speculation off: accepted drafts must reproduce the greedy chain
        # token for token — speculation changes latency, never output
        ref_eng = PagedEngine(model, params, config=config.verify_reference())
        for rid, prompt, max_new, prio in subs:
            ref_eng.submit(prompt, max_new, rid=rid, priority=prio)
        ref = ref_eng.run_until_idle()
        bad = [rid for rid, *_ in subs if done.get(rid) != ref.get(rid)]
        if bad:
            print(f"speculate token-identity: FAIL (requests {bad})")
            return 1
        print(f"speculate token-identity: ok ({len(subs)} requests)")
    if args.verify_preempt:
        # replay the exact submissions through a fresh engine with
        # preemption off: a preempted request's output must be
        # token-identical to an uninterrupted run (greedy)
        ref_eng = PagedEngine(model, params, config=config.verify_reference())
        for rid, prompt, max_new, prio in subs:
            ref_eng.submit(prompt, max_new, rid=rid, priority=prio)
        ref = ref_eng.run_until_idle()
        bad = [rid for rid, *_ in subs if done.get(rid) != ref.get(rid)]
        if bad:
            print(f"preempt token-identity: FAIL (requests {bad})")
            return 1
        print(f"preempt token-identity: ok ({len(subs)} requests)")
    if args.faults:
        fs = eng.faults.stats()
        ws = eng.watchdog.stats()
        print(f"faults: injected={fs['injected']} "
              f"corrupted={fs['corrupted_snapshots']} "
              f"recovered={eng.recovered} "
              f"failed={len(eng.sched.failed)} sweeps={ws['sweeps']}")
    if args.verify_faults:
        # replay the exact submissions through a fresh fault-free engine:
        # every request that still completed under the fault plan must be
        # token-identical — faults may fail requests, never corrupt them
        ref_eng = PagedEngine(model, params, config=config.verify_reference())
        for rid, prompt, max_new, prio in subs:
            ref_eng.submit(prompt, max_new, rid=rid, priority=prio)
        ref = ref_eng.run_until_idle()
        bad = [rid for rid in done if done[rid] != ref.get(rid)]
        if bad:
            print(f"fault token-identity: FAIL (requests {bad})")
            return 1
        print(f"fault token-identity: ok ({len(done)}/{len(subs)} "
              f"completed, {len(subs) - len(done)} faulted)")
    return status


if __name__ == "__main__":
    sys.exit(main())
