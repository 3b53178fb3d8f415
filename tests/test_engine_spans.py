"""The engine's host spans on the profiler's clock, and its row counter.

``PagedEngine.step`` is one ``engine.step`` span per tick; its parts
(schedule, inputs, dispatch, sample, advance, table pushes, admissions,
retirements, compiles) are spans nested inside it.  A tiny engine is run
under ``jax.profiler`` on the CPU and the spans are read back from the
trace with ``ProfileData``: counts against the engine's own counters,
nesting, and the request ids the admission and retirement spans carry.
``live_rows`` is checked against the rows a caller sees served.
"""

import collections
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.models.model import Model
from repro.serving import (RUNNING, CacheConfig, EngineConfig, JitCounter,
                           PagedEngine, SpecConfig)

PROMPT_LENS = [3, 5, 9, 12]
MAX_NEW = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(smoke_config(get_arch("yi-6b")),
                              dtype="float32")
    model = Model(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in PROMPT_LENS]
    return model, model.init(jax.random.key(0)), prompts


def engine(model, params, speculate=0):
    """2 slots and 4-token chunks: prompts take several mixed steps, and
    four requests refill the slots."""
    return PagedEngine(model, params, config=EngineConfig(
        slots=2, chunk=4, cache=CacheConfig(page_size=4, max_len=32),
        spec=SpecConfig(speculate=speculate)))


def profiled(directory, fn):
    """Run ``fn()`` under the profiler; the engine spans of the trace as
    ``(name, start_ns, end_ns, stats)``, in start order."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """A cold engine serving four requests under the profiler: every
    program compiles once."""
    model, params, prompts = tiny
    eng = engine(model, params)
    rids = [eng.submit(p, MAX_NEW).rid for p in prompts]
    before = eng.stats()
    spans = profiled(tmp_path_factory.mktemp("trace"), eng.run_until_idle)
    return eng, rids, before, eng.stats(), spans


def names(spans):
    return collections.Counter(s[0] for s in spans)


def test_one_step_span_per_tick(traced):
    eng, _, before, after, spans = traced
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == after["ticks"] - before["ticks"] > 0
    assert [s[3]["step_num"] for s in steps] == list(
        range(before["ticks"] + 1, after["ticks"] + 1))
    assert names(spans)["engine.schedule"] == len(steps)


def test_every_child_nests_in_its_step(traced):
    spans = traced[4]
    steps = [s for s in spans if s[0] == "engine.step"]
    for s in spans:
        if s[0] == "engine.step":
            continue
        assert any(t[1] <= s[1] and s[2] <= t[2] for t in steps), s[:3]


def test_one_dispatch_span_per_program_call(traced):
    eng, _, before, after, spans = traced
    n = names(spans)
    assert n["engine.dispatch.mixed"] == eng._prefill.calls > 0
    assert n["engine.dispatch.decode"] == eng._decode.calls > 0
    assert n["engine.dispatch.reset"] == eng._reset.calls == len(PROMPT_LENS)
    programs = after["steps"] - before["steps"]
    assert programs == eng._prefill.calls + eng._decode.calls
    for part in ("engine.inputs", "engine.sample", "engine.advance"):
        assert n[part] == programs, part


def test_a_cold_call_holds_one_compile_span(traced):
    eng, _, _, _, spans = traced
    n = names(spans)
    for counter in (eng._prefill, eng._decode, eng._reset):
        assert n[f"engine.compile.{counter.name}"] == counter.retraces == 1
    for c in (s for s in spans if s[0].startswith("engine.compile.")):
        program = c[0].rsplit(".", 1)[1]
        assert any(d[0] == f"engine.dispatch.{program}" and d[1] <= c[1]
                   and c[2] <= d[2] for d in spans), c[:3]


def test_admit_and_finish_carry_the_request(traced):
    _, rids, _, _, spans = traced
    admits = [s[3] for s in spans if s[0] == "engine.admit"]
    finishes = [s[3] for s in spans if s[0] == "engine.finish"]
    assert sorted(a["rid"] for a in admits) == sorted(rids)
    assert sorted(f["rid"] for f in finishes) == sorted(rids)
    assert {a["slot"] for a in admits} == {0, 1}
    # each admission pushes the slot's page table inside its span
    for a in (s for s in spans if s[0] == "engine.admit"):
        assert any(p[0] == "engine.push_tables" and a[1] <= p[1]
                   and p[2] <= a[2] for p in spans)


def test_a_forced_retrace_is_a_compile_span(tmp_path):
    probe = JitCounter(lambda x: x * 2, name="probe")
    probe(np.zeros(2, np.float32))          # warm: (2,) compiled

    def calls():
        probe(np.zeros(2, np.float32))
        probe(np.zeros(3, np.float32))      # a new signature: retrace
        probe(np.zeros(2, np.float32))

    n = names(profiled(tmp_path, calls))
    assert n["engine.dispatch.probe"] == 3
    assert n["engine.compile.probe"] == 1
    assert probe.retraces == 2


def test_a_warm_engine_traces_no_compile_span(tiny, tmp_path):
    model, params, prompts = tiny
    eng = engine(model, params)
    eng.submit(prompts[3], MAX_NEW)
    eng.run_until_idle()
    for p in prompts:
        eng.submit(p, MAX_NEW)
    n = names(profiled(tmp_path, eng.run_until_idle))
    assert n["engine.step"] > 0
    assert not [k for k in n if k.startswith("engine.compile.")]


@pytest.mark.parametrize("speculate", [0, 2])
def test_live_rows_counts_the_rows_each_program_serves(tiny, speculate):
    """Δ``live_rows`` equals the rows a caller sees served, summed over
    the programs run: each request that was decoding and got a token,
    and each request whose prompt advanced; ``steps`` counts the programs
    that served them."""
    model, params, prompts = tiny
    eng = engine(model, params, speculate)
    reqs = [eng.submit(p, 6) for p in prompts * 2]
    s0 = eng.stats()
    rows = programs = 0
    while not eng.sched.idle:
        seen = [(r.prefill_pos, len(r.out), r.state) for r in reqs]
        calls = eng._prefill.calls + eng._decode.calls
        eng.step()
        programs += eng._prefill.calls + eng._decode.calls - calls
        for r, (pf0, out0, st0) in zip(reqs, seen):
            rows += (r.prefill_pos > pf0) + (st0 == RUNNING
                                             and len(r.out) > out0)
    s1 = eng.stats()
    assert s1["live_rows"] - s0["live_rows"] == rows > 0
    assert s1["steps"] - s0["steps"] == programs
