"""One run of one cell: set up, warm up, measure, check, report.

Everything that belongs to one configuration, traffic mix or metric is
data or a small file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the published configuration as run,
  how it maps onto the program's ``ArchConfig``, the engine settings, the
  reference family and the correctness limit;
* ``bench/traffic/<mix>.json``: the traffic mix (``bench/traffic.py``);
* ``bench/rates/<config>.<mix>.json``: the knee an open-loop cell's rate
  is a share of (``bench/knee.py`` measures it);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.

The program under test is the serving engine (``PagedEngine``): the run
drives ``submit()`` and ``step()`` and reads the requests it returns, its
program call counters, its active slots, a copy of its ``stats()`` at
each end of the window, and its host spans in a traced run.  Weights,
traffic, the trace reduction, the work counts, the peaks and the
reference are the benchmark's own.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import shutil
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
TRACE_SECONDS = 6.0          # the least traced part of a --trace 1 window
WARM_PROMPT = 300            # compile pass: two chunks, then decode


FAILED_STATES = ("failed", "rejected", "timeout", "cancelled")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


# ------------------------------------------------------------- loading

def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(name: str):
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, cfg_entry


def family(c: dict):
    return importlib.import_module(f"bench.models.{c['reference']}")


def program_arch(c: dict):
    """The program's ``ArchConfig`` for configuration ``c``: its registry
    entry with every mapped published value set from the file."""
    from repro.configs import get_arch
    p = c["program"]
    fields = {f: c[k] for f, k in p["fields"].items()}
    fields.update(p.get("set", {}))
    return dataclasses.replace(get_arch(p["arch"]), **fields)


def open_loop_rate(c: dict, mix: dict, mix_name: str) -> float:
    knee = load_json(BENCH / "rates" / f"{c['name']}.{mix_name}.json")
    return float(mix["load_of_knee"]) * float(knee["knee_req_s"])


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- devices

def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def use_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent cache at one fixed path inside the checkout, every
    program written to it however fast it compiled."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts executables made or loaded, by JAX's own monitoring events,
    and persistent-cache hits."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles: list[float] = []
        self.hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **kw):
            if event == self._event:
                self.compiles.append(time.monotonic())

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compiles)


# ---------------------------------------------------------------- engine

def build_engine(c: dict, seed: int):
    """Weights on the device in one jitted call, and one engine."""
    import jax.numpy as jnp
    from bench import weights
    from repro.models.model import Model
    from repro.serving import (CacheConfig, EngineConfig, PagedEngine,
                               SchedulerConfig)
    arch = program_arch(c)
    model = Model(arch)
    params = weights.program_params(model.param_specs(), family(c).rules(c),
                                    seed, arch.num_layers,
                                    jnp.dtype(arch.dtype))
    e = c["engine"]
    config = EngineConfig(
        slots=e["slots"], chunk=e["chunk"], seed=0,
        sched=SchedulerConfig(max_queue=1 << 16),
        cache=CacheConfig(page_size=e["page_size"], max_len=e["max_len"]))
    return PagedEngine(model, params, config=config), params


def compile_pass(eng, vocab: int) -> None:
    """Compile every program the window runs (mixed, decode, reset, the
    sampling argmax, the table pushes) with one request of two chunks."""
    prompt = (np.arange(min(WARM_PROMPT, eng.max_len - 4)) * 7919) % vocab
    eng.submit(prompt.astype(np.int32), 3)
    eng.run_until_idle()


# ------------------------------------------------------------ the window

@dataclasses.dataclass
class Live:
    """A request the harness sent, with its own clock's stamps."""
    spec: object                 # traffic.Request
    req: object                  # the engine's ServeRequest
    due: float                   # when it was due (harness clock)
    stamps: list = dataclasses.field(default_factory=list)
    slot: int = -1               # the engine slot that served it


class Window:
    """Drives the engine through the schedule and stamps every token when
    the ``step()`` that delivered it returns."""

    def __init__(self, eng, reqs, mix: dict, annotate: bool):
        import jax
        self.eng, self.mix = eng, mix
        self.pending = list(reqs)         # not yet sent, in order
        self.live: list[Live] = []
        self.done: list[Live] = []
        self.failed: list[Live] = []
        self.calls: list[dict] = []
        self.lag: list[float] = []        # how late each open-loop submit was
        self._span = (jax.profiler.TraceAnnotation if annotate
                      else (lambda name: contextlib.nullcontext()))
        if mix["loop"] == "closed":
            self.next_of = {}
            for r in reqs:
                self.next_of.setdefault(r.client, []).append(r)
            self.pending = []

    def start(self, t0: float) -> None:
        self.t0 = t0
        if self.mix["loop"] == "closed":
            for client in sorted(self.next_of):
                self._send(self.next_of[client].pop(0), t0)

    def _send(self, spec, due: float) -> None:
        with self._span("bench.submit"):
            req = self.eng.submit(spec.prompt, spec.max_new)
        self.live.append(Live(spec=spec, req=req, due=due))

    def _due_now(self, now: float) -> None:
        while self.pending and self.t0 + self.pending[0].due <= now:
            spec = self.pending.pop(0)
            due = self.t0 + spec.due
            self.lag.append(now - due)
            self._send(spec, due)

    def run_until(self, t_end: float) -> None:
        from repro.serving.scheduler import DONE, RUNNING
        eng = self.eng
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            self._due_now(now)
            if eng.sched.idle:
                nxt = (self.t0 + self.pending[0].due) if self.pending \
                    else t_end
                with self._span("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            before = [(lv, lv.req.prefill_pos, len(lv.req.out), lv.req.state)
                      for lv in self.live]
            calls = (eng._prefill.calls, eng._decode.calls)
            with self._span("bench.step"):
                eng.step()
            t = time.monotonic()
            with self._span("bench.harvest"):
                program = ("mixed" if eng._prefill.calls > calls[0] else
                           "decode" if eng._decode.calls > calls[1] else None)
                rec = {"program": program, "t": t, "prefill": [],
                       "decode": [], "logits": 0}
                for lv, pf0, out0, st0 in before:
                    r = lv.req
                    if r.slot >= 0:
                        lv.slot = r.slot
                    n_pf = r.prefill_pos - pf0
                    if n_pf > 0:
                        rec["prefill"].append((pf0, n_pf))
                    n_new = len(r.out) - out0
                    if n_new > 0:
                        lv.stamps.extend([t] * n_new)
                        rec["logits"] += n_new
                        if st0 == RUNNING:
                            # the token fed sits at this position
                            rec["decode"].append(r.prompt_len + out0 - 1)
                if program is not None:
                    rec["live"] = len(rec["decode"]) + len(rec["prefill"])
                    self.calls.append(rec)
                keep, nxt = [], []
                for lv in self.live:
                    st = lv.req.state
                    if st == DONE:
                        self.done.append(lv)
                        q = (self.next_of.get(lv.spec.client)
                             if self.mix["loop"] == "closed" else None)
                        if q:
                            nxt.append(q.pop(0))
                    elif st in FAILED_STATES:
                        self.failed.append(lv)
                    else:
                        keep.append(lv)
                self.live = keep
                for spec in nxt:
                    self._send(spec, t)


# ---------------------------------------------------------- correctness

def sample_for_check(done: list, seed: int, most: int):
    """The finished requests the reference recomputes: the one with the
    most served tokens, then one drawn from the seed from each other slot
    that finished one, slots in a seeded order, up to ``most`` requests.
    So every slot's rows are checked, and the longest request with them."""
    if not done:
        return []
    longest = min(done, key=lambda lv: (-len(lv.req.out), lv.spec.index))
    by_slot: dict = {}
    for lv in sorted(done, key=lambda lv: lv.spec.index):
        if lv is not longest and lv.slot != longest.slot:
            by_slot.setdefault(lv.slot, []).append(lv)
    rng = np.random.default_rng(int(seed) + 1)
    slots = sorted(by_slot)
    pick = [longest]
    for i in rng.permutation(len(slots)):
        if len(pick) >= most:
            break
        cands = by_slot[slots[i]]
        pick.append(cands[rng.integers(len(cands))])
    return pick


def check_inputs(picked, rows: int, length: int):
    """Teacher-forced sequences (prompt + served tokens but the last) as
    one ``[rows, length]`` block, zero padded, so that the reference runs
    one shape in every run of a cell; the positions whose logits chose
    each served token, and those tokens."""
    tokens = np.zeros((rows, length), np.int32)
    check, served = [], []
    for i, lv in enumerate(picked):
        prompt = np.asarray(lv.req.prompt, np.int32)
        out = np.asarray(lv.req.out, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, :len(seq)] = seq
        for j in range(len(out)):
            check.append((i, len(prompt) - 1 + j))
            served.append(int(out[j]))
    return tokens, np.asarray(check, np.int32), np.asarray(served, np.int64)


def logit_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at that position."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def gap_readings(gaps: np.ndarray) -> dict:
    """The numbers a correctness limit can hold: the widest gap, the mean
    gap over every served token, and the share of served tokens that are
    not the reference's first choice."""
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "flip_share": float((gaps > 0).mean())}


def compare(readings: dict, limits: dict, failed: int):
    """Each compared number beside its limit, and whether all hold: the
    configuration's limits on the gap readings, and no failed request."""
    compared = {name: {"value": readings.get(name, float("inf")),
                       "limit": float(limit)}
                for name, limit in limits.items()}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    correct = bool(readings) and all(v["value"] <= v["limit"]
                                     for v in compared.values())
    return compared, correct


# ------------------------------------------------------------------ run

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_chip: bool = True,
        config: dict | None = None, mix: dict | None = None,
        rate: float | None = None, fault=None, keep_trace: str | None = None,
        control: bool = False) -> dict:
    """One run of ``workload``; returns the result object.  The keyword
    overrides serve the tests and ``bench/calibrate.py``: a configuration
    or mix given as data, a fixed rate, ``fault(engine)`` to break the
    timed path, and ``control`` to read the int8 control's gap too."""
    bench, cell, cfg_entry = find_cell(workload)
    import jax
    chips = cell["chips"]
    devices = check_devices(chips) if require_chip else jax.devices()[:1]
    if require_chip:
        use_compile_cache()
    compiles = CompileCounter()
    from bench import engine_trace, traffic, trace as tr

    c = config or load_json(ROOT / cfg_entry["file"])
    mix = mix or traffic.load_mix(cell["traffic"])
    if mix["loop"] == "open" and rate is None:
        rate = open_loop_rate(c, mix, cell["traffic"])
    fam = family(c)
    vocab = c["vocab_size"]

    phases = {"start": time.monotonic() - t_process}
    eng, params = build_engine(c, seed)
    jax.block_until_ready(params)
    phases["weights_engine"] = time.monotonic() - t_process
    if fault is not None:
        fault(eng)
    compile_pass(eng, vocab)
    t_compiled = time.monotonic()
    phases["compile_pass"] = t_compiled - t_process
    n = traffic.count_for(mix, mix["warmup_s"] + seconds, rate)
    reqs = traffic.make(mix, seed, n, vocab, rate)
    win = Window(eng, reqs, mix, annotate=trace)
    t0 = time.monotonic()
    win.start(t0)
    win.run_until(t0 + mix["warmup_s"])
    stats_ws = copy.deepcopy(eng.stats())
    ws = time.monotonic()
    setup_s = ws - t_process
    programs = (eng._prefill.retraces, eng._decode.retraces,
                eng._reset.retraces)
    calls_before = len(win.calls)
    tdir = None
    if trace:
        # the window's last seconds are traced: writing the trace out
        # stalls the host for seconds, so it has to come after the close.
        # In an open loop the traced part starts at an arrival, so that it
        # holds a prompt's chunks (the mixed program) besides decoding.
        tdir = Path(keep_trace) if keep_trace else TRACE_DIR
        shutil.rmtree(tdir, ignore_errors=True)
        t_trace = ws + seconds - min(seconds, TRACE_SECONDS)
        t_trace = max((win.t0 + r.due for r in win.pending
                       if win.t0 + r.due <= t_trace), default=t_trace)
        win.run_until(t_trace)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 1        # the harness's own spans
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            tt0 = time.monotonic()
            win.run_until(ws + seconds)
            tt1 = we = time.monotonic()
        jax.profiler.stop_trace()
    else:
        win.run_until(ws + seconds)
        we = time.monotonic()
    stats_we = copy.deepcopy(eng.stats())
    window_compiles = compiles.between(ws, we)
    new_programs = [a - b for a, b in zip(
        (eng._prefill.retraces, eng._decode.retraces, eng._reset.retraces),
        programs)]
    dev = devices[0]
    stats = dev.memory_stats() or {}
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0))
                      for d in devices)

    record = {
        "cell": cell, "config": c, "mix": mix, "rate": rate,
        "seconds": seconds, "window": (ws, we), "setup_s": setup_s,
        "compile_pass_s": t_compiled - t_process,
        "requests": win.done + win.failed + win.live,
        "calls": [r for r in win.calls[calls_before:]
                  if r["program"] is not None],
        "occupancy_slots": eng.slots, "lag": win.lag,
        "stats": [stats_ws, stats_we],
        "family": fam, "peaks": None, "trace": None,
    }
    attempted = len(win.done) + len(win.failed) + len(win.live)
    failed = len(win.failed)

    breakdown = None
    dev_extra = {}
    if trace:
        peaks = load_json(BENCH / "peaks.json")
        if dev.device_kind not in peaks["devices"] and require_chip:
            raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
        record["peaks"] = peaks["devices"].get(dev.device_kind)
        path = sorted(glob.glob(str(tdir / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
        ex = tr.extract(path)
        red = tr.reduce(ex)
        red["engine"] = engine_trace.exposed(ex, red["t0_ns"], red["t1_ns"])
        red["calls"] = [r for r in win.calls
                        if r["program"] is not None and tt0 <= r["t"] <= tt1]
        record["trace"] = red
        dev_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        breakdown = {"device_ops": red["top_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        if keep_trace is None:
            shutil.rmtree(tdir, ignore_errors=True)

    # ---------------- correctness: after the window, program state freed
    corr = c["correct"]
    picked = sample_for_check(win.done, seed, corr["sample_requests"])
    served_tokens = sum(len(lv.req.out) for lv in picked)
    checked_slots = sorted({lv.slot for lv in picked})
    del eng, params, win.eng
    gc.collect()
    readings, control_readings, ref_s = {}, {}, 0.0
    if picked:
        tokens, check, served = check_inputs(
            picked, corr["sample_requests"], c["engine"]["max_len"])
        t_ref = time.monotonic()
        ref = fam.logits_at(c, seed, tokens, check)
        readings = gap_readings(logit_gaps(ref, served))
        ref_s = time.monotonic() - t_ref
        if control:
            low = fam.logits_at(c, seed, tokens, check, quant="int8")
            control_readings = gap_readings(logit_gaps(ref, low.argmax(-1)))
    compared, correct = compare(readings, corr["limits"], failed)

    # ---------------- metrics, each by its own reader
    wanted = [m for m in (bench["per_layer"] if trace else bench["end_to_end"])
              if workload in m.get("workloads", [workload])]
    metrics = {}
    for m in wanted:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak,
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {
        "workload": workload, "seed": seed, "rate_req_s": rate,
        "window_compiles": window_compiles, "new_programs": new_programs,
        "cache_hits": compiles.hits, "setup_phases_s": phases,
        "checked_requests": len(picked), "checked_slots": checked_slots,
        "served_checked": served_tokens,
        "reference_s": ref_s,
        "done": len(win.done),
        "max_lag_s": max(record["lag"], default=0.0),
        "hbm_in_use": int(stats.get("bytes_in_use", 0)),
    }
    result["info"]["readings"] = readings
    if control:
        control_compared, control_correct = compare(
            control_readings, corr["limits"], failed)
        result["info"].update(control_readings=control_readings,
                              control_compared=control_compared,
                              control_correct=control_correct)
    result["compared"] = compared
    return result
