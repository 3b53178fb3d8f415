"""Serving engine: continuous batching with chunked prefill over the
uniform :class:`~repro.serving.state.LayerState` tree.

One engine instance owns

* a **state tree** (:mod:`repro.serving.state`): one LayerState per layer
  of the flat stack — paged KV pools for attention layers (full, sliding-
  window, and zamba2's weight-shared block), dense slot-row states for
  RWKV/Mamba recurrences and frozen cross-attention KV.  *Every*
  architecture in the config registry serves through this tree; there is
  no family special-casing and no legacy dense loop;
* a **priority scheduler** with admission control, aging, and
  per-request metrics (:mod:`repro.serving.scheduler`): ``QUEUED ->
  PREFILLING(k/K chunks) -> RUNNING -> DONE``, pages claimed at the
  first chunk; with ``preempt=True`` a more urgent arrival may swap a
  lower-class victim out to host (``RUNNING/PREFILLING -> PREEMPTED``,
  page contents + positions + recurrent rows snapshotted through
  ``StateTree.swap_out``) and the victim later resumes token-identically
  through the same admission gate (DESIGN.md §13);
* exactly **three compiled programs** at steady state: one *mixed step*
  (``[slots, chunk]`` — at most one prefill chunk fused with every live
  decode slot), one pure decode step (``[slots, 1]``, the fused
  paged-attention kernel path), one slot reset — a warm engine never
  retraces, whatever mix of request lengths and phases arrives.
  :class:`JitCounter` is the compilation-count hook that the tests (and
  the serve CLI's ``--repeat``) assert this with.

The mixed step is the scheduler-level restatement of Kraken's one-
uniform-dataflow thesis: a decoding slot is a length-1 prefill chunk, an
idle slot a length-0 identity row, so one fixed-shape program serves any
phase mix — and because the budget accounts decode slots before granting
the chunk, **decode never stalls behind a long prompt**: every live slot
emits a token every step, while the prompt streams in ``chunk`` tokens at
a time (DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.models.model import Model
from repro.runtime.fault_tolerance import Heartbeat, StragglerDetector
from repro.serving.faults import FaultPlan
from repro.serving.paged_kv import COPY_NONE, SwapIntegrityError
from repro.serving.prefix_cache import PrefixCache, PrefixHit
from repro.serving.scheduler import (CANCELLED, FAILED, PREFILLING, RUNNING,
                                     TIMEOUT, FIFOScheduler, ServeRequest,
                                     slo_summary, summarize)
from repro.serving.speculative import Drafter, NGramDrafter, greedy_accept
from repro.serving.state import build_state_tree, stack_is_stateable
from repro.serving.watchdog import Watchdog, WatchdogConfig


class JitCounter:
    """jax.jit wrapper that counts distinct call signatures.

    A new (shape, dtype) signature == a fresh trace+compile, so
    ``retraces`` is the compilation count the zero-retrace assertions key
    on; ``cache_size`` cross-checks against jit's own compiled-program
    cache when the running jax exposes it.

    Every call is an ``engine.dispatch.<name>`` span on the profiler's
    clock, from the signature check until the call returns; a call with a
    new signature holds an ``engine.compile.<name>`` span inside it, so a
    compile in a traced window is named.
    """

    def __init__(self, fn, *, name: str, donate_argnums=()):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self.name = name
        self._dispatch_span = f"engine.dispatch.{name}"
        self._compile_span = f"engine.compile.{name}"
        self.signatures: set = set()
        self.calls = 0

    def __call__(self, *args):
        with TraceAnnotation(self._dispatch_span):
            sig = tuple((tuple(leaf.shape), str(leaf.dtype))
                        for leaf in jax.tree.leaves(args)
                        if hasattr(leaf, "shape"))
            self.calls += 1
            if sig in self.signatures:
                return self._jit(*args)
            self.signatures.add(sig)
            with TraceAnnotation(self._compile_span):
                return self._jit(*args)

    def lower(self, *args):
        """Lower the program for ``args`` ahead of a call (``.compile()``
        it to time the compile or read the compiled HLO); the call with
        the same arguments then reuses that compile."""
        return self._jit.lower(*args)

    @property
    def retraces(self) -> int:
        return len(self.signatures)

    @property
    def cache_size(self) -> int:
        if hasattr(self._jit, "_cache_size"):
            return self._jit._cache_size()
        return len(self.signatures)


# ---------------------------------------------------------------------------
# Engine configuration: one frozen tree instead of 20+ loose kwargs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission, priority, and SLO knobs (owned by the FIFOScheduler)."""
    max_queue: int = 64
    preempt: bool = False
    aging_s: float = 30.0
    slo_ttft_s: object = None         # seconds, scalar or per-class dict
    slo_e2e_s: object = None


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """KV layout and page-pool knobs (owned by the StateTree)."""
    page_size: int = 8
    max_len: int = 64
    pool_pages: int | None = None
    overcommit: float = 1.0
    prefix_cache: bool = False


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding (DESIGN.md §15)."""
    speculate: int = 0
    drafter: Drafter | None = None


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Fault tolerance (DESIGN.md §14): deadlines, injection, watchdog."""
    deadline_s: float | None = None
    watchdog: WatchdogConfig | bool | None = None
    plan: FaultPlan | None = None
    heartbeat: Heartbeat | str | None = None


# legacy PagedEngine(**kwargs) name -> (sub-config field | None, field name)
_LEGACY_KWARGS = {
    "slots": (None, "slots"), "chunk": (None, "chunk"),
    "step_budget": (None, "step_budget"),
    "temperature": (None, "temperature"), "seed": (None, "seed"),
    "decode_kernel": (None, "decode_kernel"),
    "moe_gemm": (None, "moe_gemm"),
    "max_queue": ("sched", "max_queue"), "preempt": ("sched", "preempt"),
    "aging_s": ("sched", "aging_s"), "slo_ttft_s": ("sched", "slo_ttft_s"),
    "slo_e2e_s": ("sched", "slo_e2e_s"),
    "page_size": ("cache", "page_size"), "max_len": ("cache", "max_len"),
    "pool_pages": ("cache", "pool_pages"),
    "overcommit": ("cache", "overcommit"),
    "prefix_cache": ("cache", "prefix_cache"),
    "speculate": ("spec", "speculate"), "drafter": ("spec", "drafter"),
    "deadline_s": ("fault", "deadline_s"), "watchdog": ("fault", "watchdog"),
    "faults": ("fault", "plan"), "heartbeat": ("fault", "heartbeat"),
}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The whole PagedEngine surface as one frozen tree.

    ``PagedEngine(model, params, config=EngineConfig(...))`` is the
    primary constructor; the historical flat kwargs still work through
    :meth:`from_kwargs` (with a ``DeprecationWarning``) so existing call
    sites keep running.  :meth:`validate` centralizes the invariant
    checks that used to live scattered through ``__init__`` and returns
    the *resolved* config (chunk clamped, step_budget defaulted) — the
    engine reads everything off that.
    """
    slots: int = 4
    chunk: int | None = None          # prefill chunk width (None: max_len)
    step_budget: int | None = None    # tokens/step (None: slots + chunk)
    temperature: float = 0.0
    seed: int = 0
    decode_kernel: str | None = None  # paged-attention mode (None: auto)
    moe_gemm: str | None = None       # grouped expert GEMM mode (None: auto)
    sched: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    spec: SpecConfig = dataclasses.field(default_factory=SpecConfig)
    fault: FaultConfig = dataclasses.field(default_factory=FaultConfig)

    @classmethod
    def from_kwargs(cls, **kwargs) -> "EngineConfig":
        """Build a config from the legacy flat kwarg namespace (the
        pre-EngineConfig ``PagedEngine.__init__`` signature)."""
        top: dict = {}
        sub: dict[str, dict] = {"sched": {}, "cache": {}, "spec": {},
                                "fault": {}}
        for name, val in kwargs.items():
            where = _LEGACY_KWARGS.get(name)
            if where is None:
                raise TypeError(
                    f"PagedEngine got an unexpected keyword {name!r}")
            section, field = where
            (top if section is None else sub[section])[field] = val
        return cls(sched=SchedulerConfig(**sub["sched"]),
                   cache=CacheConfig(**sub["cache"]),
                   spec=SpecConfig(**sub["spec"]),
                   fault=FaultConfig(**sub["fault"]), **top)

    def validate(self) -> "EngineConfig":
        """Check every cross-field invariant and resolve the derived
        defaults; returns the resolved copy the engine runs on."""
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        max_len = self.cache.max_len
        chunk = int(self.chunk) if self.chunk is not None else max_len
        if chunk <= 0:
            raise ValueError("chunk must be positive")
        # admission caps prompts at max_len, so no chunk can ever carry
        # more real tokens — a wider program would be pure padding compute
        chunk = min(chunk, max_len)
        step_budget = int(self.step_budget) if self.step_budget is not None \
            else self.slots + chunk
        if step_budget < max(chunk, self.slots):
            # below `chunk` a chunk could never issue, even on an otherwise
            # idle engine (prefill deadlock); below `slots` a full decode
            # step would overrun the budget — decode is committed work the
            # scheduler never throttles, so the budget must cover it for
            # "tokens per step" to be a true ceiling
            raise ValueError(
                f"step_budget {step_budget} < max(chunk={chunk}, "
                f"slots={self.slots}): the budget must fit one bare chunk "
                "and the full decode load")
        if self.spec.speculate < 0:
            raise ValueError("speculate must be >= 0")
        if self.spec.speculate and self.temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only: the accept rule "
                "matches drafts against the argmax chain, so speculate > 0 "
                "requires temperature == 0")
        if self.cache.pool_pages is not None and self.cache.pool_pages < 1:
            raise ValueError("pool_pages must be >= 1")
        return dataclasses.replace(self, chunk=chunk,
                                   step_budget=step_budget,
                                   spec=dataclasses.replace(
                                       self.spec,
                                       speculate=int(self.spec.speculate)))

    def verify_reference(self) -> "EngineConfig":
        """The matching *reference* config for A/B verify replays: same
        shapes and kernel modes, but speculation, preemption, and the
        whole fault surface (injection, deadlines, watchdog, heartbeat)
        off — the features whose token-identity the replays prove, plus
        anything that would race the live engine's side files."""
        return dataclasses.replace(
            self,
            sched=dataclasses.replace(self.sched, preempt=False),
            spec=SpecConfig(),
            fault=FaultConfig())


class PagedEngine:
    """Chunked-prefill continuous-batching server over the uniform
    LayerState tree.

    Serves every architecture whose stack slots expose a
    :class:`~repro.serving.state.LayerState` — which, by construction of
    the slot vocabulary, is every config in the registry: dense,
    sliding-window, local/global, MoE-FFN, RWKV, Mamba/hybrid, cross-attn
    VLM, and int8-KV variants alike.

    ``chunk`` is the prefill chunk width (default: ``max_len`` — every
    admissible prompt in one chunk); ``step_budget`` the per-step token
    budget (default ``slots + chunk``): the scheduler accounts one token
    per live decode slot first and grants the chunk (charged its real
    token count) only from the remainder, so decode is never displaced.
    The budget is a true ceiling on tokens issued per step — the
    constructor requires it to cover ``max(chunk, slots)``, since decode
    is committed work the scheduler never throttles.
    """

    @staticmethod
    def supports(model: Model) -> bool:
        """Whether this model can serve through the engine — true iff every
        stack slot kind has a LayerState implementation (the protocol's
        coverage predicate; fails loudly for a future slot kind added
        without one)."""
        return stack_is_stateable(model)

    @classmethod
    def pool_geoms(cls, model: Model, *, slots: int, page_size: int,
                   max_len: int) -> list[tuple[int, int, int, int]]:
        """The distinct ``(slots, logical_len, head_dim, window)``
        paged-decode cell geometries an engine with these knobs traces —
        the first three are the identity the ``op_kind="paged_decode"``
        autotune cache is keyed on, the window is the masking protocol the
        measurement must run under.  Derived from the state tree itself
        (zamba2's weight-shared pools included), so ``serve --autotune``
        warmup can never drift from what the decode program looks up."""
        return build_state_tree(model, slots=slots, page_size=page_size,
                                max_len=max_len).paged_geoms()

    def __init__(self, model: Model, params, *,
                 config: EngineConfig | None = None, **kwargs):
        from repro.kernels import kraken_moe_gemm as _mg
        from repro.kernels import paged_attention as _pa
        if config is not None and kwargs:
            raise TypeError(
                "pass either config=EngineConfig(...) or the legacy flat "
                f"kwargs, not both (got config and {sorted(kwargs)})")
        if config is None:
            if kwargs:
                warnings.warn(
                    "PagedEngine(model, params, **kwargs) is deprecated; "
                    "pass config=EngineConfig(...) (legacy kwargs map via "
                    "EngineConfig.from_kwargs)",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig.from_kwargs(**kwargs)
        config = config.validate()
        self.config = config
        cfg = model.cfg
        if not self.supports(model):   # the one eligibility predicate
            raise NotImplementedError(
                "a stack slot of this model has no LayerState "
                "implementation (repro.serving.state) — add one; the "
                "engine has no fallback path")
        self.model, self.params, self.cfg = model, params, cfg
        slots, max_len = config.slots, config.cache.max_len
        self.slots, self.page_size = slots, config.cache.page_size
        self.max_len = max_len
        self.chunk = config.chunk          # resolved by validate()
        self.step_budget = config.step_budget
        self.temperature = config.temperature
        self._key = jax.random.key(config.seed)
        # --- speculative decoding (DESIGN.md §15) --------------------------
        # Greedy-only (validate() enforces it): the accept walk compares
        # drafts against the argmax chain, which *is* the sampled stream
        # only at temperature 0 — anything else would silently change the
        # output distribution.
        self.speculate = config.spec.speculate
        self.drafter: Drafter | None = config.spec.drafter \
            if config.spec.drafter is not None \
            else (NGramDrafter() if self.speculate else None)
        # priority scheduling + preempt-to-host (DESIGN.md §13): the
        # scheduler owns the policy (aged priority order, victim choice),
        # the engine owns the mechanism (swap-out/swap-in through the
        # LayerState tree); SLO targets are seconds, scalar or per-class
        self.preempt_enabled = bool(config.sched.preempt)
        self.slo_ttft_s = config.sched.slo_ttft_s
        self.slo_e2e_s = config.sched.slo_e2e_s
        self.sched = FIFOScheduler(max_queue=config.sched.max_queue,
                                   max_total_len=max_len,
                                   aging_s=config.sched.aging_s)

        # --- the uniform state tree ---------------------------------------
        self.state = build_state_tree(model, slots=slots,
                                      page_size=self.page_size,
                                      max_len=max_len,
                                      overcommit=config.cache.overcommit,
                                      pool_pages=config.cache.pool_pages)
        self.pools = self.state.init_device()
        # Draft-write ring clamp (DESIGN.md §15): a committed write past a
        # ring's logical length wraps by design, but a *rejected draft*
        # that wrapped has already destroyed history the rolled-back
        # state still needs — unrecoverable.  So drafts are only granted
        # while every fed position stays below the smallest paged ring
        # (full-attention pools never bind: admission caps positions at
        # max_len <= logical; sliding-window pools stop drafting at the
        # first wrap and fall back to plain decode).  Row-only trees
        # (pure recurrent) have no ring to protect.
        rings = [ring for (_, ring, _, _) in self.state.paged_geoms()]
        self._draft_ring = min(rings) if rings else None
        self._has_rows = self.state.has_rows

        # --- fault tolerance (DESIGN.md §14) --------------------------------
        # The watchdog instance always exists (it owns the step-fault
        # recovery policy); periodic invariant sweeps only run when the
        # caller opted in (`watchdog=True` or an explicit config).
        self.default_deadline_s = config.fault.deadline_s
        self.faults = config.fault.plan
        watchdog = config.fault.watchdog
        self.watchdog_enabled = bool(watchdog)
        cfg_wd = watchdog if isinstance(watchdog, WatchdogConfig) else \
            WatchdogConfig()
        if not self.watchdog_enabled:
            cfg_wd = WatchdogConfig(cadence=0,
                                    max_retries=cfg_wd.max_retries,
                                    backoff_ticks=cfg_wd.backoff_ticks,
                                    quarantine_ticks=cfg_wd.quarantine_ticks)
        self.watchdog = Watchdog(self, cfg_wd)
        self.heartbeat = Heartbeat(config.fault.heartbeat, interval_s=1.0) \
            if isinstance(config.fault.heartbeat, str) \
            else config.fault.heartbeat
        self.straggler = StragglerDetector()

        # --- prefix cache (DESIGN.md §12) ---------------------------------
        # Enabled only when every layer state is cacheable (full-attention
        # paged pools — one shared allocator group); recurrent/windowed
        # architectures report non-cacheability through the state tree, so
        # rwkv6/zamba2/vlm serve with a structural hit rate of 0 even when
        # the flag is on.
        self.prefix_cache_requested = bool(config.cache.prefix_cache)
        self.prefix_cache: PrefixCache | None = None
        self._cache_alloc = None
        if self.prefix_cache_requested:
            grp = self.state.cacheable_group()
            if grp is not None:
                self._cache_alloc = self.state.allocators[grp]
                self.prefix_cache = PrefixCache(self._cache_alloc,
                                                page_size=self.page_size)

        # Resolve the decode attention implementation once (``decode_kernel``
        # argument > $KRAKEN_PAGED_DECODE > auto: fused on TPU, dense-gather
        # reference elsewhere) and pin it into this engine's trace — two
        # engines with different kernels coexist in one process.  The MoE
        # expert-GEMM mode resolves the same way (``moe_gemm`` >
        # $KRAKEN_MOE_GEMM > auto: grouped on TPU, einsum reference
        # elsewhere); for non-MoE models it is recorded but never traced.
        with _pa.use_paged_decode_mode(config.decode_kernel):
            self.decode_kernel = _pa.resolve_paged_decode_mode()
        with _mg.use_moe_gemm_mode(config.moe_gemm):
            self.moe_gemm = _mg.resolve_moe_gemm_mode()

        # --- the engine's three compiled programs --------------------------
        def mixed_fn(params, pools, tokens, positions, lengths):
            # always returns (last, greedy, pools): the per-column argmax
            # chain is what speculative verify accepts drafts against,
            # and returning it unconditionally keeps ONE mixed program
            # shape whether or not this engine speculates (verify *is*
            # the chunk program — DESIGN.md §15)
            view = self.state.decode_view(pools, positions[:, 0])
            with _pa.use_paged_decode_mode(self.decode_kernel), \
                    _mg.use_moe_gemm_mode(self.moe_gemm):
                return model.chunk_step(params, view, tokens, positions,
                                        lengths, return_greedy=True)

        def decode_fn(params, pools, tokens, pos, live):
            # decode_view is the protocol's per-layer hook for producing
            # what decode consumes (identity for every state kind today —
            # the model reads pools and slot rows natively; the prefix
            # cache deliberately does NOT hang here: a cache hit is pure
            # page-table mapping, so decode consumes shared pages through
            # the same pools with no view transform — the seam stays free
            # for speculative decode)
            view = self.state.decode_view(pools, pos)
            with _pa.use_paged_decode_mode(self.decode_kernel), \
                    _mg.use_moe_gemm_mode(self.moe_gemm):
                return model.decode_step(params, view, tokens, pos,
                                         lengths=live)

        def reset_fn(pools, slot_ids, src, dst, resume):
            # freed-slot hygiene + the CoW content copy, one fixed-shape
            # program: the reset runs against the *staged* table (the
            # admitted slot's shared prefix entries sentineled, so cached
            # pages survive), then a full-hit fork duplicates its last
            # shared page with positions >= resume masked.  Sentinel
            # (COPY_NONE) ids make the copy drop — cache-off admissions
            # run the very same program, so a cache hit never adds a
            # fourth compiled program shape.
            pools = self.state.reset(pools, slot_ids)
            return self.state.copy_pages(pools, src, dst, resume)

        # ``_prefill`` is the mixed-step program (the only one that ever
        # prefills); the names keep the stats/CLI surface stable
        self._prefill = JitCounter(mixed_fn, name="mixed",
                                   donate_argnums=(1,))
        self._decode = JitCounter(decode_fn, name="decode",
                                  donate_argnums=(1,))
        self._reset = JitCounter(reset_fn, name="reset", donate_argnums=(0,))

        # --- per-slot host state ------------------------------------------
        self.active: list[ServeRequest | None] = [None] * slots
        self._cur = np.zeros((slots, 1), np.int32)
        self._pos = np.zeros((slots,), np.int32)
        self._emit_step = np.zeros((slots,), np.int64)
        self._rid = 0
        self.ticks = 0              # step() calls, program or not — the
        #                             clock faults/backoff/quarantine key on
        #                             (keying on `steps` would livelock
        #                             run_until_idle while everything queued
        #                             is backing off: no program, no step)
        self.steps = 0              # programs run (mixed + pure decode)
        self.live_rows = 0          # live decode rows + the prefill row,
        #                             summed over programs run
        self.decode_steps = 0       # steps that advanced >= 1 decode slot
        self._issued = 0            # real tokens issued across all steps
        self._max_stall = 0         # worst decode gap observed, in steps
        self._prefill_tok = 0       # prompt tokens actually prefilled
        self._cached_tok = 0        # prompt tokens skipped via cache hits
        self._cow_forks = 0         # copy-on-write page forks performed
        self.preemptions = 0        # slots swapped out to host
        self.resumes = 0            # preempted requests swapped back in
        self.recovered = 0          # step faults survived via requeue
        self.timeouts = 0           # requests expired past their deadline
        self.cancels = 0            # requests cancelled by their caller
        self.unservable = 0         # queue heads failed as never-admittable
        self.swap_rejects = 0       # corrupted snapshots rejected at swap-in
        self.spec_steps = 0         # verify steps that carried >= 1 draft
        self.spec_drafted = 0       # draft tokens fed through verify
        self.spec_accepted = 0      # drafts the argmax chain accepted
        self.spec_emitted = 0       # tokens emitted by draft-carrying steps

    # ---------------------------------------------------------------- API
    def submit(self, prompt, max_new: int, rid: int | None = None,
               priority: int = 0,
               deadline_s: float | None = None) -> ServeRequest:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if rid is None:
            # auto rids must never collide with a live caller-supplied rid
            # (the scheduler would reject the engine's own assignment)
            live = ({r.rid for r in self.sched.queue}
                    | {r.rid for r in self.sched.running.values()})
            while self._rid in live:
                self._rid += 1
            rid, self._rid = self._rid, self._rid + 1
        req = ServeRequest(rid=rid, prompt=prompt, max_new=int(max_new),
                           priority=int(priority),
                           deadline_s=deadline_s if deadline_s is not None
                           else self.default_deadline_s)
        # all rejection classes (over-long prompt, prompt + max_new beyond
        # the KV budget, empty prompt, max_new < 1, queue full, duplicate
        # rid against a live request) go through the scheduler's one reject
        # path — stamped with REJECTED so the metrics stay meaningful
        self.sched.submit(req)
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel request ``rid`` in *any* non-terminal lifecycle state —
        QUEUED/PREEMPTED (in the queue), PREFILLING/RUNNING (in a slot).
        Every resource the request held is reclaimed (page decrefs,
        slot, host swap snapshot); partial output survives on the
        request for the caller.  False when ``rid`` is unknown or
        already terminal — cancellation is idempotent, never an error."""
        req = next((r for r in self.sched.queue if r.rid == rid), None)
        if req is None:
            req = next((r for r in self.active
                        if r is not None and r.rid == rid), None)
        if req is None:
            return False
        self._terminate(req, CANCELLED, "cancelled by caller")
        self.cancels += 1
        return True

    def run_until_idle(self, log=None) -> dict[int, list[int]]:
        while not self.sched.idle:
            self.step()
        if self.faults is not None:
            # a drained engine returns every injected resource: hostage
            # pages still held go back to their free lists
            self.faults.drain()
        if self.watchdog_enabled:
            self.watchdog.sweep()   # the at-drain invariant oracle
        if log is not None:
            log(self.report())
        return {r.rid: list(r.out) for r in self.sched.done}

    # ------------------------------------------------------------- engine
    def step(self) -> None:
        """One scheduler iteration: expire deadlines, admit the queue
        head into a free slot (page claim at first chunk), then issue
        one fixed-shape program — the mixed step (every live decode slot
        + at most one prefill chunk, decode accounted against the
        budget first) when a chunk fits, the pure fused-kernel decode
        step otherwise.  A fault injected at the pre-program seam is
        handed to the watchdog's recovery policy instead of crashing
        the batch (DESIGN.md §14).

        Each tick is one ``engine.step`` span on the profiler's clock
        (``step_num`` = ``ticks``), its parts the child spans
        ``engine.schedule``, ``engine.inputs``, ``engine.dispatch.*``,
        ``engine.sample``, ``engine.advance`` and ``engine.push_tables``
        (``bench/engine_trace.py`` splits the chip's idle time by them)."""
        self.ticks += 1
        with StepTraceAnnotation("engine.step", step_num=self.ticks):
            with TraceAnnotation("engine.schedule"):
                picked = self._schedule()
            if picked is None:
                return
            dec, pf = picked
            t0 = time.perf_counter()
            self.steps += 1
            self.live_rows += len(dec) + (pf is not None)
            if pf is not None or (self.speculate and dec):
                # with speculation on, decode always rides the mixed
                # program (a speculating slot is a multi-token chunk;
                # verify is the chunk step) — the pure decode program
                # simply goes unused, so the engine still compiles at
                # most three programs
                self._mixed_step(dec, pf)
            else:
                self._decode_step(dec)
            dt = time.perf_counter() - t0
            self.straggler.record(dt)
            if self.heartbeat is not None:
                self.heartbeat.beat(self.ticks, steps=self.steps,
                                    queued=len(self.sched.queue),
                                    running=len(self.sched.running),
                                    done=len(self.sched.done))

    def _schedule(self) -> tuple[list[int], int | None] | None:
        """Expire, sweep and admit, then pick this tick's rows: the live
        decode slots and the prefill slot whose chunk fits the budget.
        None when no program runs this tick (nothing live, or a fault at
        the pre-program seam was recovered)."""
        if self.faults is not None:
            self.faults.on_tick(self)
        self._expire()
        self.watchdog.maybe_sweep()
        self._admit()
        dec = [i for i, r in enumerate(self.active)
               if r is not None and r.state == RUNNING]
        pf = next((i for i, r in enumerate(self.active)
                   if r is not None and r.state == PREFILLING), None)
        # budget ordering (DESIGN.md §11/§15): committed decode work first
        # — one token per slot, or the slot's whole pending tail under
        # speculation (committed tokens a rolled-back recurrent state must
        # re-feed; never throttled, like decode itself) — then the prefill
        # chunk from the remainder, and only leftover budget buys drafts.
        committed = sum(self._n_pending(i) for i in dec) if self.speculate \
            else len(dec)
        if pf is not None:
            # budget: decode slots are accounted first, and the chunk is
            # charged its *real* token count — a final partial chunk only
            # costs what remains of the prompt, not the padded width
            r = self.active[pf]
            remaining = min(self.chunk, r.prompt_len - r.prefill_pos)
            if committed + remaining > self.step_budget:
                pf = None
        if not dec and pf is None:
            return None
        if self.faults is not None:
            # the pre-program seam: slots are selected but the jitted call
            # has not consumed (donated) the pools, so a fault raised here
            # is fully recoverable — swap the offending slot out and retry
            try:
                self.faults.before_program(self)
            except Exception as e:   # noqa: BLE001 — any injected fault
                self._recover(e, dec, pf)
                return None
        return dec, pf

    # ------------------------------------------------- failure edges (§14)
    def _expire(self) -> None:
        """Terminate every request past its wall-clock deadline, in any
        non-terminal state: queued (incl. PREEMPTED — its snapshot is
        dropped) or live in a slot (pages released, slot freed)."""
        now = self.sched.clock()
        stale = [r for r in list(self.sched.queue)
                 + [r for r in self.active if r is not None]
                 if r.deadline_s is not None
                 and now - r.t_submit > r.deadline_s]
        for req in stale:
            self._terminate(req, TIMEOUT,
                            f"deadline {req.deadline_s:g}s exceeded")
            self.timeouts += 1

    def _terminate(self, req: ServeRequest, status: str,
                   error: str | None = None) -> None:
        """One reclamation path for every abnormal end: release the slot's
        pages/rows if the request holds one (decrefs shared pages — the
        prefix cache keeps its own holds), then hand the bookkeeping to
        the scheduler.  Eager host work only: no fourth program."""
        slot = req.slot
        if slot >= 0 and self.active[slot] is req:
            self.active[slot] = None
            self.state.release(slot)
            self._push_tables()
        self.sched.terminate(req, status, error)

    def _recover(self, exc: Exception, dec: list[int],
                 pf: int | None) -> None:
        """The step-fault handler: the watchdog decides retry vs fail for
        the offending slot's request (the prefilling slot when one was
        selected — prefill drives the step — else the first decode
        slot).  Retry rides the existing PREEMPTED machinery: swap out,
        requeue with backoff (``hold_until_tick``), quarantine the slot;
        resume is the standard admission-gate swap-in.  Retries
        exhausted means FAILED, never a crashed batch."""
        slot = pf if pf is not None else dec[0]
        req = self.active[slot]
        verdict = self.watchdog.on_step_fault(req, exc)
        if verdict == "retry":
            self.preempt(slot)
            self.recovered += 1
        else:
            self._terminate(req, FAILED,
                            f"retries exhausted after {req.retries - 1} "
                            f"recoveries ({req.error})")

    def _admit(self) -> None:
        # Chunks issue one per step, so at most one request prefills at a
        # time — claiming pages for a second would only pressure the pool
        # (and park a live-table slot in pure-decode steps).  Admission ==
        # page claim at first chunk.  The admission candidate is the
        # scheduler's priority head (aged class order; strict FIFO with
        # one class) — and with preemption enabled, a head of a strictly
        # higher class than some active request may swap a victim out to
        # host rather than wait behind it.
        head = self.sched.head(self.ticks)
        if head is None:
            return
        if self.preempt_enabled and self._blocked(head):
            victim = self.sched.pick_victim(
                head, [r for r in self.active if r is not None])
            if victim is not None:
                self.preempt(victim.slot)
        if any(r is not None and r.state == PREFILLING for r in self.active):
            return
        free = self.watchdog.usable_slots(
            [i for i, a in enumerate(self.active) if a is None])
        if not free:
            return
        head = self.sched.head(self.ticks)  # the preempted victim may lead
        if head is None:
            return
        if head.swap is not None:
            # a preempted request resumes through the same admission gate
            # (all-private page claim — its swapped state needs the full
            # row), bypassing the prefix-cache *match*: the host snapshot
            # already holds everything a hit could offer.  Cache *eviction*
            # still runs (via the admission predicate) so cached-but-idle
            # pages can never starve a resume.
            if not self._can_admit_head(None):
                return
            with TraceAnnotation("engine.admit", rid=head.rid, slot=free[0]):
                self.sched.pop(head, free[0])
                try:
                    self._resume(head)
                except SwapIntegrityError as e:
                    # a corrupted/truncated host snapshot is rejected
                    # before any device write: undo the claim (slot,
                    # pages, tables) and fail the request — never resume
                    # garbage
                    slot = head.slot
                    self.active[slot] = None
                    self.state.release(slot)
                    self._push_tables()
                    self.sched.terminate(head, FAILED, str(e))
                    self.swap_rejects += 1
            return
        # one cache lookup per admission attempt, on the head only —
        # match takes no references, so a rejected admission drops it cold
        hit: PrefixHit | None = None
        if self.prefix_cache is not None:
            h = self.prefix_cache.match(head.prompt)
            hit = h if h.is_hit else None
        kept = 0
        if hit is not None:
            kept = len(hit.pages) - (1 if hit.fork_logical is not None else 0)
        if not self.state.can_ever_admit(shared=kept):
            # structurally unservable: the claim exceeds what the whole
            # pool could supply even empty — waiting can never help, and
            # leaving it at the head would livelock run_until_idle.
            # Deliberately *never* keyed on transient free-page counts
            # (live neighbours / injected exhaustion mean "wait").
            self._terminate(head, FAILED,
                            "unservable: the request needs more pages than "
                            "the pool can ever supply")
            self.unservable += 1
            return
        if not self._can_admit_head(hit):
            return
        with TraceAnnotation("engine.admit", rid=head.rid, slot=free[0]):
            self._claim(self.sched.pop(head, free[0]), hit)

    def _claim(self, req: ServeRequest, hit: PrefixHit | None) -> None:
        """Seat a popped request in its slot: prefix-cache pages mapped,
        freed-slot reset (and the CoW copy) run, tables pushed."""
        # a cache hit admits straight to PREFILLING(k/K): the shared pages
        # map into the slot's leading logical rows and prefill resumes at
        # the page boundary (full hits recompute only the last token for
        # its logits — inside a CoW-forked copy of the last shared page)
        if self.prefix_cache is not None:
            self.prefix_cache.record(req.prompt_len, hit)
        req.cached_tokens = hit.resume if hit else 0
        req.prefill_pos = req.cached_tokens
        req.n_chunks = -(-req.prompt_len // self.chunk)
        remaining = -(-(req.prompt_len - req.prefill_pos) // self.chunk)
        req.chunks_done = req.n_chunks - remaining
        self.active[req.slot] = req
        self.state.admit(req.slot, shared=hit.pages if hit else ())
        src = dst = int(COPY_NONE)
        resume = 0
        if hit is not None and hit.fork_logical is not None:
            src, dst = self._cache_alloc.cow_fork(req.slot, hit.fork_logical)
            resume = hit.resume
            self._cow_forks += 1
        self._cached_tok += req.cached_tokens
        # freed-state hygiene before any new writes, one fixed-shape reset
        # (slot ids padded with -1 drop sentinels, so the program never
        # retraces): KV states invalidate the pages the slot now owns,
        # recurrent states zero the slot's row — a refilled slot never
        # sees its predecessor.  The table pushed *for the reset* masks
        # this slot's cache-shared entries to a sentinel so their positions
        # survive; the CoW copy (fused into the same program, sentinel ids
        # when no fork) then lands in the forked page's fresh slot.  The
        # full table follows once the pools are clean.
        self.pools = self.state.push_tables(self.pools,
                                            private_only_slot=req.slot)
        ids = np.full((self.slots,), -1, np.int32)
        ids[0] = req.slot
        self.pools = self._reset(self.pools, jnp.asarray(ids),
                                 jnp.asarray([src], jnp.int32),
                                 jnp.asarray([dst], jnp.int32),
                                 jnp.asarray([resume], jnp.int32))
        self._push_tables()

    def _can_admit_head(self, hit: PrefixHit | None) -> bool:
        """Admission predicate for the queue head: physical-page accounting.
        ``kept`` shared pages are already resident (the cache holds them),
        so the head only needs ``pages_per_slot - kept`` fresh physical
        pages — a logical-page count would over-reject shared-prefix
        requests.  Eviction (refcount-aware LRU) runs first if the free
        list is short, pinning the pages this very hit is about to map."""
        kept = 0
        if hit is not None:
            kept = len(hit.pages) - (1 if hit.fork_logical is not None else 0)
        if self.prefix_cache is not None:
            a = self._cache_alloc
            need = a.pages_per_slot - kept
            if a.free_pages < need:
                self.prefix_cache.evict(
                    need, protect=frozenset(hit.pages if hit else ()))
        return self.state.can_admit(shared=kept)

    # -------------------------------------------------- preempt-to-host
    def _blocked(self, head: ServeRequest) -> bool:
        """Whether the admission head cannot be admitted as the engine
        stands: every slot occupied, or a slot free but the page claim
        does not fit even after prefix-cache eviction
        (:meth:`_can_admit_head` runs the refcount-aware LRU first, so
        preemption is the last resort, never a cache shortcut)."""
        if all(r is not None for r in self.active):
            return True
        return not self._can_admit_head(None)

    def preempt(self, slot: int) -> ServeRequest:
        """Swap ``slot`` out to host and requeue its request as PREEMPTED.

        The snapshot (page contents + positions + recurrent rows, via
        ``StateTree.swap_out`` — one geometry for every state kind) plus
        the host decode cursor is everything resume needs to continue
        token-identically; the slot's pages/rows are released (shared
        prefix-cache pages survive through the cache's own refcounts) and
        the freed table rows sentineled on device.  All host-side and
        eager work — the engine still compiles exactly three programs."""
        req = self.active[slot]
        if req is None or req.state not in (PREFILLING, RUNNING):
            raise ValueError(f"slot {slot} holds nothing preemptible")
        snap = self.state.swap_out(self.pools, slot)
        if self.faults is not None:
            # the swap_corrupt seam: an armed event flips one byte of
            # this snapshot (digest left stale) — resume must reject it
            snap = self.faults.maybe_corrupt(snap)
        req.swap = {
            "state": snap,
            "cur": int(self._cur[slot, 0]),
            "pos": int(self._pos[slot]),
            "running": req.state == RUNNING,
        }
        req.preemptions += 1
        self.active[slot] = None
        self.state.release(slot)
        self._push_tables()
        self.sched.requeue(req)
        self.preemptions += 1
        return req

    def _resume(self, req: ServeRequest) -> None:
        """Swap a preempted request back in: claim an all-private page
        row, run the one reset program (freed-slot hygiene, sentinel CoW
        ids — the same shape every admission runs), restore the host
        snapshot, and re-enter the lifecycle where it left off —
        PREFILLING(k/K) with k at the swap point, or straight back to
        RUNNING with its decode cursor."""
        slot = req.slot
        self.active[slot] = req
        self.state.admit(slot)
        self.pools = self.state.push_tables(self.pools)
        ids = np.full((self.slots,), -1, np.int32)
        ids[0] = slot
        none = jnp.asarray([int(COPY_NONE)], jnp.int32)
        self.pools = self._reset(self.pools, jnp.asarray(ids), none, none,
                                 jnp.asarray([0], jnp.int32))
        self.pools = self.state.swap_in(self.pools, slot, req.swap["state"])
        self._push_tables()
        if req.swap["running"]:
            req.state = RUNNING
            self._cur[slot, 0] = req.swap["cur"]
            self._pos[slot] = req.swap["pos"]
            self._emit_step[slot] = self.steps   # swap gap is not a stall
        # else: PREFILLING resumes at req.prefill_pos through the normal
        # chunked mixed step — k/K progress fields survived the round trip
        req.swap = None
        req.recovering = False   # a watchdog retry that made it back in
        self.resumes += 1

    def _mixed_step(self, dec: list[int], pf: int | None) -> None:
        req = self.active[pf] if pf is not None else None
        n = min(self.chunk, req.prompt_len - req.prefill_pos) \
            if req is not None else 0
        with TraceAnnotation("engine.inputs"):
            tokens, positions, lengths, meta, snaps = \
                self._pack_mixed(dec, pf, n)
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(lengths))
        last, greedy, self.pools = self._prefill(self.params, self.pools,
                                                 *args)
        self._issued += int(sum(lengths[i] for i in dec)) + n
        self._prefill_tok += n
        nxt = self._sample(last)
        with TraceAnnotation("engine.advance"):
            if meta:
                finished = self._advance_speculative(
                    dec, np.asarray(greedy), meta, snaps)
            else:
                finished = self._advance_decode(dec, nxt)
            if pf is not None:
                req.prefill_pos += n
                req.chunks_done += 1
                if req.prefill_pos >= req.prompt_len:
                    # prefill complete: register the prompt's full page
                    # chunks under the cache chain (already-cached chunks
                    # just touch LRU, so a CoW fork's private copy never
                    # displaces the original).  Only the *prompt* —
                    # committed tokens — ever reaches the chain; draft
                    # tokens live in decode rows and are structurally
                    # invisible here (DESIGN.md §15).
                    if self.prefix_cache is not None:
                        self.prefix_cache.insert(
                            req.prompt,
                            self._cache_alloc.slot_pages(req.slot))
                    # last chunk: its top-row logits are the first token
                    req.state = RUNNING
                    req.out.append(int(nxt[pf]))
                    req.t_first = self.sched.clock()
                    self._cur[pf, 0] = int(nxt[pf])
                    self._pos[pf] = req.prompt_len
                    self._emit_step[pf] = self.steps
                    if len(req.out) >= req.max_new:  # max_new=1: done now
                        self._finish(pf)
                        finished += 1
        if finished:
            self._push_tables()

    def _pack_mixed(self, dec: list[int], pf: int | None, n: int):
        """The mixed program's host inputs: ``[slots, chunk]`` tokens and
        positions, per-row lengths, and under speculation each row's
        (pending, drafts) and the recurrent snapshots to roll back to."""
        w = self.chunk
        tokens = np.zeros((self.slots, w), np.int32)
        positions = np.zeros((self.slots, w), np.int32)
        lengths = np.zeros((self.slots,), np.int32)
        ar = np.arange(w, dtype=np.int32)
        meta: dict[int, tuple[int, np.ndarray]] = {}
        snaps: dict[int, object] = {}
        if self.speculate and dec:
            # verify-as-chunk packing (DESIGN.md §15): each speculating
            # slot's row carries its committed pending tail (re-fed after
            # a recurrent rollback; normally just the current token)
            # followed by fresh drafts from whatever budget decode and
            # the prefill chunk left over
            budget = self.step_budget - n \
                - sum(self._n_pending(i) for i in dec)
            for i in dec:
                pend = self._pending(i)
                drafts = self._draft_for(i, len(pend), budget)
                budget -= len(drafts)
                if len(drafts) and self._has_rows:
                    # rows can only rewind by restore — snapshot the
                    # last-accepted state before the program consumes
                    # (donates) the pools
                    snaps[i] = self.state.spec_snapshot(self.pools, i)
                row = np.concatenate([pend, drafts]) \
                    if len(drafts) else pend
                tokens[i, :len(row)] = row
                positions[i] = self._pos[i] + ar
                lengths[i] = len(row)
                meta[i] = (len(pend), drafts)
        else:
            for i in dec:
                tokens[i, 0] = self._cur[i, 0]
                positions[i] = self._pos[i] + ar
                lengths[i] = 1
        if pf is not None:
            req = self.active[pf]
            start = req.prefill_pos
            tokens[pf, :n] = req.prompt[start:start + n]
            positions[pf] = start + ar
            lengths[pf] = n
        return tokens, positions, lengths, meta, snaps

    # ------------------------------------------- speculative decode (§15)
    def _n_pending(self, i: int) -> int:
        """Committed tokens not yet reflected in slot ``i``'s device
        state: the stream suffix past the write cursor.  1 in plain
        decode (the current token); > 1 only after a recurrent rollback
        re-queued an accepted run for re-feeding."""
        req = self.active[i]
        return req.prompt_len + len(req.out) - int(self._pos[i])

    def _pending(self, i: int) -> np.ndarray:
        """The committed tokens slot ``i`` must feed next, in stream
        order — ``pending[0]`` lands at position ``_pos[i]``."""
        req = self.active[i]
        stream = np.concatenate(
            [req.prompt, np.asarray(req.out, np.int32)])
        return stream[int(self._pos[i]):].astype(np.int32)

    def _draft_for(self, i: int, n_pend: int, budget: int) -> np.ndarray:
        """Propose drafts for slot ``i`` under every clamp: the chunk
        width (the row must fit the program), the leftover token budget,
        the request's remaining output (no point drafting past
        ``max_new`` — the correction token always rides along), and the
        ring bound (a rejected draft that wrapped would have destroyed
        history rollback still needs)."""
        req = self.active[i]
        k = min(self.speculate, self.chunk - n_pend, budget,
                req.max_new - len(req.out) - 1)
        if self._draft_ring is not None:
            k = min(k, self._draft_ring - (int(self._pos[i]) + n_pend))
        if k <= 0:
            return np.zeros((0,), np.int32)
        hist = np.concatenate([req.prompt, np.asarray(req.out, np.int32)])
        drafts = np.asarray(self.drafter.propose(hist, k),
                            np.int32).reshape(-1)
        return drafts[:k]

    def _advance_speculative(self, dec: list[int], greedy: np.ndarray,
                             meta: dict, snaps: dict) -> int:
        """The accept/rollback walk for every verified slot (DESIGN.md
        §15).  Accept the longest draft prefix matching the argmax chain
        plus the first correction token — the stream plain greedy decode
        would emit, so token identity holds by construction.  On any
        rejection, rewind through ``StateTree.truncate``: pure-paged
        trees keep the accepted positions and mask the rejected tail;
        row-bearing trees restore the pre-verify snapshot and re-feed
        the newly committed run next chunk (it re-accepts
        deterministically, so every verify step still nets >= 1 fresh
        token)."""
        if dec:
            self.decode_steps += 1
        finished = 0
        for i in dec:
            req = self.active[i]
            n_pend, drafts = meta[i]
            k = len(drafts)
            a, toks = greedy_accept(drafts, greedy[i], n_pend - 1)
            toks = toks[:req.max_new - len(req.out)]
            base = int(self._pos[i])
            if a == k:
                # full accept (plain decode is the k == 0 case): every
                # fed token is committed, the state simply advances
                self._pos[i] = base + n_pend + k
            elif self._has_rows:
                # rows hold state after *all* fed tokens — restore the
                # last-accepted snapshot (paged leaves re-mask to base;
                # the accepted run re-feeds as pending next chunk)
                self.pools = self.state.truncate(self.pools, i, base,
                                                 snap=snaps[i])
            else:
                # pure paged: the accepted prefix's KV is already exactly
                # right — keep it, mask only the rejected positions
                new_pos = base + n_pend + a
                self.pools = self.state.truncate(self.pools, i, new_pos)
                self._pos[i] = new_pos
            req.out.extend(toks)
            self._cur[i, 0] = int(req.out[-1])
            if k > 0:
                self.spec_steps += 1
                self.spec_drafted += k
                self.spec_accepted += a
                self.spec_emitted += len(toks)
                req.drafted += k
                req.accepted += a
            self._max_stall = max(self._max_stall,
                                  int(self.steps - self._emit_step[i] - 1))
            self._emit_step[i] = self.steps
            if len(req.out) >= req.max_new:
                self._finish(i)
                finished += 1
        return finished

    def _decode_step(self, dec: list[int]) -> None:
        with TraceAnnotation("engine.inputs"):
            live = np.zeros((self.slots,), np.int32)
            live[dec] = 1
            args = (jnp.asarray(self._cur), jnp.asarray(self._pos),
                    jnp.asarray(live))
        logits, self.pools = self._decode(self.params, self.pools, *args)
        self._issued += len(dec)
        nxt = self._sample(logits)
        with TraceAnnotation("engine.advance"):
            finished = self._advance_decode(dec, nxt)
        if finished:
            # sentinel the freed page-table rows on device before the next
            # step: an idle slot's KV writes must drop, not land in pages
            # a later request may own.  (Recurrent slot-row states need no
            # sentinel — an idle slot only ever writes its own row, which
            # the next admission resets and overwrites.)  One push per
            # step, however many finished.
            self._push_tables()

    def _advance_decode(self, dec: list[int], nxt: np.ndarray) -> int:
        """Emit one token for every live decode slot; returns #finished."""
        if dec:
            self.decode_steps += 1
        finished = 0
        for i in dec:
            req = self.active[i]
            req.out.append(int(nxt[i]))
            self._cur[i, 0] = int(nxt[i])
            self._pos[i] += 1
            # a live slot that emits every step has gap 0; anything larger
            # is a real decode stall (the property the budget must prevent)
            self._max_stall = max(self._max_stall,
                                  int(self.steps - self._emit_step[i] - 1))
            self._emit_step[i] = self.steps
            if len(req.out) >= req.max_new:
                self._finish(i)
                finished += 1
        return finished

    def _finish(self, slot: int) -> None:
        """Retire a slot (host bookkeeping only — the caller pushes the
        updated tables to device once per wave)."""
        req = self.active[slot]
        with TraceAnnotation("engine.finish", rid=req.rid):
            self.active[slot] = None
            self.sched.complete(req)
            self.state.release(slot)

    def _push_tables(self) -> None:
        with TraceAnnotation("engine.push_tables"):
            self.pools = self.state.push_tables(self.pools)

    def _sample(self, logits) -> np.ndarray:
        """The next token of every row: the argmax or categorical dispatch
        and the blocking host read of its result."""
        with TraceAnnotation("engine.sample"):
            if self.temperature > 0:
                self._key, sub = jax.random.split(self._key)
                return np.asarray(jax.random.categorical(
                    sub, logits.astype(jnp.float32) / self.temperature,
                    axis=-1))
            return np.asarray(jnp.argmax(logits, axis=-1))

    # ------------------------------------------------------------ metrics
    @property
    def allocators(self):
        return self.state.allocators

    def stats(self) -> dict:
        cache = self.prefix_cache
        return {
            "prefill_calls": self._prefill.calls,
            "prefill_retraces": self._prefill.retraces,
            "prefill_cache_size": self._prefill.cache_size,
            "steps": self.steps,
            "live_rows": self.live_rows,
            "decode_steps": self.decode_steps,
            "decode_retraces": self._decode.retraces,
            "decode_kernel": self.decode_kernel,
            "moe_gemm": self.moe_gemm if self.cfg.num_experts else None,
            "chunk": self.chunk,
            "step_budget": self.step_budget,
            "budget_util": self._issued / max(1, self.steps * self.step_budget),
            "max_decode_stall": self._max_stall,
            "free_pages": self.state.free_pages,
            "prefix_cache": cache is not None,
            "prefix_lookups": cache.lookups if cache else 0,
            "prefix_hits": cache.hits if cache else 0,
            "prefix_hit_rate": round(cache.hit_rate, 4) if cache else 0.0,
            "prefill_tokens": self._prefill_tok,
            "cached_prefill_tokens": self._cached_tok,
            "cow_forks": self._cow_forks,
            "cache_pages": cache.cached_pages if cache else 0,
            "cache_evictions": cache.evictions if cache else 0,
            "speculate": self.speculate,
            "spec_steps": self.spec_steps,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": round(
                self.spec_accepted / self.spec_drafted, 4)
            if self.spec_drafted else 0.0,
            "spec_accepted_per_step": round(
                self.spec_emitted / self.spec_steps, 4)
            if self.spec_steps else 0.0,
            "preempt": self.preempt_enabled,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "ticks": self.ticks,
            "recovered": self.recovered,
            "timeouts": self.timeouts,
            "cancels": self.cancels,
            "unservable": self.unservable,
            "swap_rejects": self.swap_rejects,
            "failed_total": len(self.sched.failed),
            "straggler_steps": self.straggler.flagged,
            "watchdog": self.watchdog.stats() if self.watchdog_enabled
            else None,
            "faults": self.faults.stats() if self.faults is not None
            else None,
            "slo": self.slo(),
        }

    def slo(self) -> dict:
        """Per-priority-class TTFT/e2e distribution (p50/p99) with
        attainment against the engine's configured targets."""
        return slo_summary(self.sched.done, ttft_target_s=self.slo_ttft_s,
                           e2e_target_s=self.slo_e2e_s)

    def report(self) -> str:
        s = self.stats()
        m = summarize(self.sched.done + self.sched.rejected
                      + self.sched.failed)
        cache = ""
        if s["prefix_cache"]:
            cache = (f"| prefix hit rate={s['prefix_hit_rate'] * 100:.1f}% "
                     f"({s['cached_prefill_tokens']} tok cached, "
                     f"{s['cow_forks']} cow forks) ")
        spec = ""
        if self.speculate:
            spec = (f"| speculate k={s['speculate']}: "
                    f"accept rate={s['spec_accept_rate'] * 100:.1f}% "
                    f"accepted/step={s['spec_accepted_per_step']:.2f} "
                    f"({s['spec_accepted']}/{s['spec_drafted']} drafts) ")
        pre = ""
        if self.preempt_enabled:
            pre = (f"| preemptions={s['preemptions']} "
                   f"(resumes={s['resumes']}) ")
        ft = ""
        if (self.faults is not None or self.watchdog_enabled
                or s["failed_total"] or s["timeouts"] or s["cancels"]):
            ft = (f"| faults: recovered={s['recovered']} "
                  f"timeout={s['timeouts']} cancelled={s['cancels']} "
                  f"failed={s['failed_total'] - s['timeouts'] - s['cancels']} ")
        slo = ""
        for cls, ent in sorted(s["slo"].items()):
            seg = (f"p{cls}: ttft p50/p99="
                   f"{ent['ttft_p50_s'] * 1e3:.0f}/"
                   f"{ent['ttft_p99_s'] * 1e3:.0f} ms")
            if "ttft_attained" in ent:
                seg += (f" ({ent['ttft_attained'] * 100:.0f}% <= "
                        f"{ent['ttft_target_s'] * 1e3:.0f} ms)")
            if "e2e_attained" in ent:
                seg += (f", e2e {ent['e2e_attained'] * 100:.0f}% <= "
                        f"{ent['e2e_target_s'] * 1e3:.0f} ms")
            slo += f"| slo {seg} "
        return (f"served {m.get('done', 0)} req "
                f"({m.get('rejected', 0)} rejected), "
                f"{m.get('tokens', 0)} tok @ {m.get('tok_s', 0.0):.1f} tok/s "
                f"| ttft mean {m.get('ttft_mean_s', 0.0) * 1e3:.0f} ms "
                f"| prefill retraces={s['prefill_retraces']} "
                f"decode retraces={s['decode_retraces']} "
                f"| max decode stall={s['max_decode_stall']} steps "
                f"{cache}{spec}{pre}{ft}{slo}"
                f"| budget util={s['budget_util'] * 100:.1f}% "
                f"(chunk={s['chunk']}, budget={s['step_budget']})")
