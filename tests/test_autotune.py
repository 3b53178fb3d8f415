"""Autotuner subsystem tests: candidate parity, cache round-trips, modes.

Three contracts:

* every candidate the search enumerates is *correct* — any (bm, bk, bn,
  schedule) the autotuner may pick must reproduce the ref.py oracle under
  Pallas interpret mode (property-swept over random shapes);
* the persistent cache round-trips: tune -> save -> reload in a fresh
  instance (fresh-process simulation) yields the identical TileConfig, and
  corrupted / version-mismatched files degrade to a warning, never a crash;
* ``choose_tiles(mode=...)`` routing: "model" is the static pick, "cached"
  falls back to the model on a miss and replays persisted winners on a hit
  (even winners the model would never pick — the override contract).
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import tuning  # noqa: E402
from repro.core import elastic  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.tuning import cache as tcache  # noqa: E402
from repro.tuning import search  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_policy():
    """Keep the process-wide tuning policy pristine across tests."""
    yield
    tuning.set_tile_mode(None)
    tuning.set_tile_cache(tcache.TileCache(path=None))


# ---------------------------------------------------------------------------
# Candidate parity vs the oracle
# ---------------------------------------------------------------------------

def _check_all_candidates(m, k, n, top_n=2):
    rng = np.random.default_rng(m * 1_000_003 + k * 1_009 + n)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    want = ref.matmul(a, b)
    cands = search.select_candidates(m, k, n, in_bytes=4, top_n=top_n)
    schedules = {c.schedule for c in cands}
    assert schedules == {"weight_stationary", "output_stationary"}, cands
    for cfg in cands:
        got = search.run_gemm_candidate(a, b, cfg, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=f"candidate {cfg} diverged from oracle at "
                    f"({m},{k},{n})")


@settings(max_examples=6, deadline=None)
@given(m=st.integers(1, 160), k=st.integers(1, 160), n=st.integers(1, 160))
def test_every_candidate_matches_oracle(m, k, n):
    _check_all_candidates(m, k, n)


@pytest.mark.parametrize("m,k,n", [
    (8, 128, 128),     # exact single tile
    (129, 257, 130),   # off-by-one over every tile boundary
    (1, 1, 1),         # degenerate
])
def test_candidates_match_oracle_edge_shapes(m, k, n):
    _check_all_candidates(m, k, n)


def test_select_candidates_covers_both_schedules_and_is_model_ranked():
    cands = search.select_candidates(512, 4096, 4096, top_n=3)
    per = {}
    for c in cands:
        per[c.schedule] = per.get(c.schedule, 0) + 1
    assert per["weight_stationary"] <= 3 and per["output_stationary"] <= 3
    assert elastic.model_best(cands) == elastic.choose_tiles(
        512, 4096, 4096, mode="model")


# ---------------------------------------------------------------------------
# Cache round-trip / resilience
# ---------------------------------------------------------------------------

def test_autotune_persist_reload_identical(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = tcache.TileCache(path)
    won = search.autotune_gemm(24, 40, 56, cache=cache, top_n=1, reps=1)

    # Fresh instance = fresh process namespace: nothing shared but the file.
    cache2 = tcache.TileCache(path)
    key = tcache.cache_key("gemm", 24, 40, 56, "float32",
                           search.backend_name())
    assert cache2.get(key) == won

    # A hit must short-circuit measurement entirely.
    def boom(*a, **kw):
        raise AssertionError("cache hit must not re-benchmark")

    orig = search.benchmark_candidates
    search.benchmark_candidates = boom
    try:
        again = search.autotune_gemm(24, 40, 56, cache=cache2, top_n=1, reps=1)
    finally:
        search.benchmark_candidates = orig
    assert again == won
    assert cache2.hits >= 2


def test_cache_entry_records_measurement_metadata(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = tcache.TileCache(path)
    search.autotune_gemm(16, 16, 16, cache=cache, top_n=2, reps=1)
    [entry] = list(tcache.TileCache(path).entries.values())
    assert entry["measured_us"] > 0
    assert entry["candidates_timed"] >= 2
    assert "model_pick" in entry and "agrees_with_model" in entry


def test_corrupted_cache_file_warns_not_crashes(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text("{ this is not json")
    with pytest.warns(UserWarning, match="unreadable"):
        cache = tcache.TileCache(str(path))
    assert len(cache) == 0


def test_version_mismatch_ignored_with_warning(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"version": 999, "entries": {"x": {}}}))
    with pytest.warns(UserWarning, match="version"):
        cache = tcache.TileCache(str(path))
    assert len(cache) == 0
    # And a non-dict payload:
    path.write_text(json.dumps([1, 2, 3]))
    with pytest.warns(UserWarning, match="version"):
        assert len(tcache.TileCache(str(path))) == 0


def test_malformed_entry_is_a_miss_not_a_crash(tmp_path):
    path = tmp_path / "plans.json"
    key = tcache.cache_key("gemm", 8, 8, 8, "float32", "cpu-interpret")
    path.write_text(json.dumps(
        {"version": tcache.CACHE_VERSION, "entries": {key: {"bm": "nope"}}}))
    cache = tcache.TileCache(str(path))
    with pytest.warns(UserWarning, match="malformed"):
        assert cache.get(key) is None
    assert cache.misses == 1


def test_cache_save_is_atomic_and_reloadable(tmp_path):
    path = str(tmp_path / "sub" / "dir" / "plans.json")  # dirs auto-created
    cache = tcache.TileCache(path)
    cfg = elastic.choose_tiles(64, 64, 64, mode="model")
    cache.put("k", cfg, measured_us=1.5)
    cache.save()
    blob = json.loads(open(path).read())
    assert blob["version"] == tcache.CACHE_VERSION
    assert tcache.TileCache(path).get("k") == cfg


# ---------------------------------------------------------------------------
# choose_tiles mode routing
# ---------------------------------------------------------------------------

def test_mode_model_is_default_and_unchanged():
    a = elastic.choose_tiles(512, 4096, 4096, in_bytes=2)
    b = elastic.choose_tiles(512, 4096, 4096, in_bytes=2, mode="model")
    assert a == b
    assert a.schedule == "weight_stationary" and a.utilization == 1.0


def test_mode_cached_falls_back_to_model_on_miss():
    tuning.set_tile_cache(tcache.TileCache(path=None))
    got = elastic.choose_tiles(512, 4096, 4096, mode="cached")
    assert got == elastic.choose_tiles(512, 4096, 4096, mode="model")
    assert tuning.get_tile_cache().misses == 1


def test_mode_cached_replays_persisted_winner_even_if_model_disagrees():
    cache = tuning.set_tile_cache(tcache.TileCache(path=None))
    # Fabricate a measured winner the model would never pick.
    odd = elastic._make_config(512, 4096, 4096, 128, 128, 128,
                               "output_stationary", 2)
    key = tcache.cache_key("gemm", 512, 4096, 4096, "float32",
                           search.backend_name())
    cache.put(key, odd, measured_us=1.0)
    got = elastic.choose_tiles(512, 4096, 4096, mode="cached",
                               dtype_name="float32")
    assert got == odd != elastic.choose_tiles(512, 4096, 4096, mode="model")


def test_invalid_mode_raises():
    with pytest.raises(ValueError, match="unknown tile mode"):
        elastic.choose_tiles(8, 8, 8, mode="fastest")
    with pytest.raises(ValueError, match="tile mode"):
        tuning.set_tile_mode("fastest")


def test_policy_env_and_setter(monkeypatch):
    tuning.set_tile_mode(None)
    monkeypatch.delenv(tuning.TILE_MODE_ENV, raising=False)
    assert tuning.get_tile_mode() == "model"
    monkeypatch.setenv(tuning.TILE_MODE_ENV, "cached")
    assert tuning.get_tile_mode() == "cached"
    monkeypatch.setenv(tuning.TILE_MODE_ENV, "bogus")
    assert tuning.get_tile_mode() == "model"
    tuning.set_tile_mode("autotune")
    assert tuning.get_tile_mode() == "autotune"


def test_gemm_cell_tile_plan_routes_mode():
    from repro.core.unified import matmul_cell
    cache = tuning.set_tile_cache(tcache.TileCache(path=None))
    cell = matmul_cell(512, 4096, 4096)
    odd = elastic._make_config(512, 4096, 4096, 128, 128, 128,
                               "output_stationary", 2)
    # tile_plan's default lookup dtype follows in_bytes (2 -> bfloat16),
    # matching the keys the serve/train warmers write for bf16 configs.
    key = tcache.cache_key("gemm", 512, 4096, 4096,
                           tuning.dtype_name_for(2), search.backend_name())
    cache.put(key, odd)
    assert cell.tile_plan(mode="cached") == odd
    assert cell.tile_plan(mode="model") != odd
    # explicit dtype_name targets the matching namespace
    assert cell.tile_plan(mode="cached", dtype_name="float32") != odd


def test_conv_direct_replays_cached_bco(tmp_path):
    """A persisted conv_direct winner is consumed by kraken_conv2d_direct
    when the policy is 'cached' (bco left unset)."""
    from repro.kernels.kraken_conv import kraken_conv2d_direct
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 6, 4)),
                    jnp.float32)
    kern = jnp.asarray(np.random.default_rng(1).normal(size=(3, 3, 4, 8)),
                       jnp.float32)
    cache = tuning.set_tile_cache(tcache.TileCache(path=None))
    oh = ow = 4
    m_eq, k_eq = 1 * oh * ow, 4 * 3 * 3
    key = tcache.cache_key("conv_direct", m_eq, k_eq, 8, "float32",
                           search.backend_name())
    cache.put(key, elastic._make_config(m_eq, k_eq, 8, 8, 128, 256,
                                        "output_stationary", 4))
    tuning.set_tile_mode("cached")
    out = kraken_conv2d_direct(x, kern, interpret=True)
    assert cache.hits == 1          # the bco came from the cache (bn=256)
    want = ref.conv2d(x, kern)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_autotune_gemm_interpret_cap_falls_back_to_model():
    cache = tcache.TileCache(path=None)
    def boom(*a, **kw):
        raise AssertionError("oversized cell must not be measured off-TPU")
    orig = search.benchmark_candidates
    search.benchmark_candidates = boom
    try:
        got = search.autotune_gemm(4096, 4096, 4096, cache=cache, reps=1)
    finally:
        search.benchmark_candidates = orig
    assert got == elastic.choose_tiles(4096, 4096, 4096, mode="model",
                                       in_bytes=4)
    assert len(cache) == 0          # unmeasured picks are never persisted


def test_autotune_cells_reports_hits_on_second_pass(tmp_path):
    from repro.core.unified import matmul_cell
    cells = [matmul_cell(16, 24, 32, name="a"), matmul_cell(8, 8, 8, name="b")]
    cache = tcache.TileCache(str(tmp_path / "plans.json"))
    first = tuning.autotune_cells(cells, cache=cache, top_n=1, reps=1)
    assert [s for _, _, s in first] == ["tuned", "tuned"]
    # Fresh instance, same file: everything hits, plans identical.
    cache2 = tcache.TileCache(str(tmp_path / "plans.json"))
    second = tuning.autotune_cells(cells, cache=cache2, top_n=1, reps=1)
    assert [s for _, _, s in second] == ["hit", "hit"]
    assert [p for _, p, _ in first] == [p for _, p, _ in second]


def test_autotune_cells_skips_oversized_cells_off_tpu():
    from repro.core.unified import matmul_cell
    big = matmul_cell(4096, 4096, 64000, name="prod_logits")
    [(_, plan, status)] = tuning.autotune_cells(
        [big], cache=tcache.TileCache(path=None), reps=1)
    assert status == "skipped"
    # off-TPU default dtype is float32 -> the model pick is priced at 4B
    assert plan == elastic.choose_tiles(4096, 4096, 64000, mode="model",
                                        in_bytes=4)


def test_autotune_conv_round_trip(tmp_path):
    path = str(tmp_path / "plans.json")
    bco = search.autotune_conv((1, 8, 8, 4), (3, 3, 4, 8),
                               cache=tcache.TileCache(path), reps=1)
    cache2 = tcache.TileCache(path)
    assert search.autotune_conv((1, 8, 8, 4), (3, 3, 4, 8),
                                cache=cache2, reps=1) == bco
    assert cache2.hits == 1 and cache2.misses == 0
    # conv_direct entries live in their own key namespace
    assert all(k.startswith("conv_direct:") for k in cache2.entries)


def test_autotune_paged_decode_round_trip(tmp_path):
    """op_kind="paged_decode": tune -> persist -> fresh-instance reload
    replays the winning pages_per_block without re-measuring, under its own
    key namespace keyed m/k/n <- slots/logical_len/head_dim."""
    path = str(tmp_path / "plans.json")
    ppb = search.autotune_paged_decode(2, 16, 8, page_size=4, kv_heads=2,
                                       q_heads=4, reps=1,
                                       cache=tcache.TileCache(path))
    assert 1 <= ppb <= 4
    cache2 = tcache.TileCache(path)
    key = tcache.cache_key("paged_decode", 2, 16, 8, "float32",
                           search.backend_name())
    entry = cache2.peek(key)
    assert entry is not None and entry["bn"] == ppb
    assert entry["kind"] == "paged_decode_ppb" and entry["measured_us"] > 0
    assert all(k.startswith("paged_decode:") for k in cache2.entries)
    assert search.autotune_paged_decode(2, 16, 8, page_size=4, kv_heads=2,
                                        q_heads=4, reps=1,
                                        cache=cache2) == ppb
    assert cache2.hits == 1 and cache2.misses == 0


def test_resolve_pages_per_block_modes(tmp_path):
    """The kernel-side ppb lookup honors the process-wide tile policy:
    "model" ignores the cache, "cached" replays a persisted winner (even one
    the static default would never pick) and falls back on a miss."""
    from repro.core import elastic
    from repro.kernels.paged_attention import (default_pages_per_block,
                                               resolve_pages_per_block)
    geom = dict(slots=2, logical_len=16, head_dim=8, page_size=4,
                max_pages=4, dtype_name="float32")
    pool = dict(kv_heads=1, head_dim=8, itemsize=4)
    static = default_pages_per_block(4, 4, **pool)
    assert resolve_pages_per_block(**geom) == static   # mode=model default

    cache = tuning.set_tile_cache(tcache.TileCache(path=None))
    key = tcache.cache_key("paged_decode", 2, 16, 8, "float32",
                           search.backend_name())
    cfg = elastic._make_config(2, 16, 8, elastic.SUBLANE, 128, 3,
                               "output_stationary", 4)
    cache.put(key, cfg, extra={"page_size": 4,         # ppb=3: not the default
                               "kv_heads": 1})
    tuning.set_tile_mode("cached")
    assert resolve_pages_per_block(**geom) == 3
    assert resolve_pages_per_block(**{**geom, "logical_len": 32,
                                     "max_pages": 8}) == \
        default_pages_per_block(4, 8, **pool)          # miss -> static
    # same m/k/n from a different page layout: the key under-determines the
    # cell, so the entry's recorded page_size gates the replay
    assert resolve_pages_per_block(**{**geom, "page_size": 2,
                                     "max_pages": 8}) == \
        default_pages_per_block(2, 8, **pool)
    # ... and so does its kv_heads: a grid step fetches every head of a page
    assert resolve_pages_per_block(**{**geom, "kv_heads": 2}) == \
        default_pages_per_block(4, 4, **{**pool, "kv_heads": 2})


def test_paged_decode_ppb_fits_the_vmem_budget():
    """The untuned ppb keeps the 512-token stripe while a step's double-
    buffered K + V blocks (every KV head of each page) fit the budget, and
    lowers it for geometries with many KV heads; the autotuner's lattice
    never offers a ppb over the budget."""
    from repro.kernels.paged_attention import (KV_BLOCK_VMEM_BYTES,
                                               default_pages_per_block,
                                               page_block_bytes)
    yi = dict(kv_heads=4, head_dim=128, itemsize=2)      # yi-6b, bf16 pool
    assert default_pages_per_block(16, 128, **yi) == 32
    assert 32 * page_block_bytes(16, **yi) == 2 << 20
    wide = dict(kv_heads=32, head_dim=128, itemsize=2)
    ppb = default_pages_per_block(16, 128, **wide)
    assert 1 <= ppb < 32
    assert ppb * page_block_bytes(16, **wide) <= KV_BLOCK_VMEM_BYTES
    assert (ppb + 1) * page_block_bytes(16, **wide) > KV_BLOCK_VMEM_BYTES
    geoms = (yi, wide, dict(kv_heads=8, head_dim=128, itemsize=1),
             dict(kv_heads=32, head_dim=64, itemsize=2))
    for g in geoms:
        for ps, mp in ((8, 8), (16, 128), (1, 4096)):
            cands = search.paged_decode_candidates(ps, mp, **g)
            assert default_pages_per_block(ps, mp, **g) in cands
            assert all(c * page_block_bytes(ps, **g) <= KV_BLOCK_VMEM_BYTES
                       for c in cands)


def test_paged_decode_entry_without_kv_heads_is_a_miss():
    """A ``paged_decode`` entry tuned for the grid step per KV head (no
    ``kv_heads`` recorded) never replays onto the folded kernel: it is a
    counted miss, as is an entry for another head count."""
    cache = tcache.TileCache(path=None)
    key = tcache.cache_key("paged_decode", 8, 2048, 128, "bfloat16",
                           search.backend_name())
    cfg = elastic._make_config(8, 2048, 128, elastic.SUBLANE, 2048, 64,
                               "output_stationary", 4)
    look = dict(page_size=16, kv_heads=4, max_pages=128)
    cache.put(key, cfg, extra={"page_size": 16})
    assert search.lookup_paged_decode(cache, key, **look) is None
    assert (cache.hits, cache.misses) == (0, 1)
    cache.put(key, cfg, extra={"page_size": 16, "kv_heads": 4})
    assert search.lookup_paged_decode(cache, key, **look) == 64
    assert (cache.hits, cache.misses) == (1, 1)
    assert search.lookup_paged_decode(cache, key,
                                      **{**look, "kv_heads": 8}) is None
    assert (cache.hits, cache.misses) == (1, 2)


def test_serving_cells_dedup_and_coverage():
    from repro.configs import get_arch, smoke_config
    from repro.core.unified import serving_cells
    cfg = smoke_config(get_arch("yi-6b"))
    cells = serving_cells(cfg, slots=4, prompt_len=12, cache_len=64)
    shapes = [(c.m, c.k, c.n) for c in cells]
    assert len(shapes) == len(set(shapes))          # deduped
    assert len(cells) >= 3                           # report has >= 3 rows
    names = " ".join(c.name for c in cells)
    assert "prefill" in names and "decode" in names and "logits" in names
    # Only cells the kraken_gemm tile path can replay belong on the
    # work-list: attention score/context run via the flash kernels.
    from repro.core.unified import KRAKEN_GEMM_KINDS
    assert all(c.kind in KRAKEN_GEMM_KINDS for c in cells)
