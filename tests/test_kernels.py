"""Per-kernel validation: Pallas (interpret mode) vs the ref.py oracles,
swept over shapes and dtypes, plus elastic-tiling properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import elastic
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rel_err(got, want):
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    return float(jnp.abs(g - w).max()) / (float(jnp.abs(w).max()) + 1e-6)


# ---------------------------------------------------------------------------
# kraken_gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 16, 24), (128, 256, 128), (200, 300, 100), (33, 1000, 65),
    (1, 4096, 256), (512, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kraken_gemm_shapes_dtypes(m, k, n, dtype):
    a = jnp.asarray(RNG.normal(size=(m, k)), dtype)
    b = jnp.asarray(RNG.normal(size=(k, n)), dtype)
    out = ops.kraken_matmul(a, b, interpret=True, use_pallas=True)
    want = ref.matmul(a, b)
    assert _rel_err(out, want) < (1e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("activation", [None, "relu", "silu", "gelu"])
def test_kraken_gemm_epilogue(activation):
    a = jnp.asarray(RNG.normal(size=(64, 96)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(96, 80)), jnp.float32)
    bias = jnp.asarray(RNG.normal(size=(80,)), jnp.float32)
    out = ops.kraken_matmul(a, b, bias=bias, activation=activation,
                            interpret=True, use_pallas=True)
    want = ref.matmul(a, b, bias=bias, activation=activation)
    assert _rel_err(out, want) < 1e-4


@pytest.mark.parametrize("activation,with_bias", [
    (None, False), (None, True), ("silu", False), ("gelu", True)])
def test_kraken_gemm_grad_matches_reference(activation, with_bias):
    """The custom VJP (backward GEMMs through the same kernel) is the
    autodiff of the oracle, bias and epilogue included."""
    a = jnp.asarray(RNG.normal(size=(40, 72)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(72, 56)), jnp.float32)
    bias = jnp.asarray(RNG.normal(size=(56,)), jnp.float32) if with_bias \
        else None

    def grads(matmul):
        def loss(a, b, bias):
            return jnp.sum(jnp.tanh(matmul(a, b, bias=bias,
                                           activation=activation)))
        return jax.grad(loss, argnums=(0, 1, 2))(a, b, bias)

    got = grads(lambda *x, **kw: ops.kraken_matmul(
        *x, **kw, interpret=True, use_pallas=True))
    want = grads(ref.matmul)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
        else:
            assert _rel_err(g, w) < 1e-4


def test_both_schedules_agree():
    from repro.kernels.kraken_gemm import kraken_gemm
    a = jnp.asarray(RNG.normal(size=(256, 384)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(384, 256)), jnp.float32)
    ws = kraken_gemm(a, b, bm=128, bk=384, bn=128,
                     schedule="weight_stationary", interpret=True)
    os_ = kraken_gemm(a, b, bm=128, bk=128, bn=128,
                      schedule="output_stationary", interpret=True)
    assert _rel_err(ws, os_) < 1e-5


# ---------------------------------------------------------------------------
# kraken_conv (uniform lowering conv -> GEMM)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(n=2, h=8, w=8, ci=3, co=5, k=3, s=1, p=1),
    dict(n=1, h=16, w=16, ci=4, co=8, k=5, s=2, p=2),
    dict(n=2, h=7, w=9, ci=2, co=4, k=1, s=1, p=0),
    dict(n=1, h=12, w=12, ci=3, co=7, k=7, s=2, p=3),
])
def test_kraken_conv2d(case):
    c = case
    x = jnp.asarray(RNG.normal(size=(c["n"], c["h"], c["w"], c["ci"])), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(c["k"], c["k"], c["ci"], c["co"])), jnp.float32)
    pad = ((c["p"], c["p"]), (c["p"], c["p"]))
    out = ops.kraken_conv2d(x, k, stride=(c["s"], c["s"]), padding=pad,
                            interpret=True, use_pallas=True)
    want = ref.conv2d(x, k, stride=(c["s"], c["s"]), padding=pad)
    assert _rel_err(out, want) < 1e-4


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,s,d,win,bq,bkv", [
    (1, 2, 2, 256, 64, 64, 128, 128),
    (2, 4, 2, 256, 64, 100, 64, 64),     # GQA via index maps
    (1, 8, 2, 512, 128, 4096, 128, 128),  # window > seq (degenerates causal)
    (1, 2, 1, 256, 64, 1, 64, 32),        # window 1 (diagonal only)
])
def test_swa_attention(b, h, hkv, s, d, win, bq, bkv):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    out = ops.swa_attention(q, k, v, window=win, use_pallas=True,
                            interpret=True, block_q=bq, block_kv=bkv)
    want = ops.swa_attention(q, k, v, window=win, use_pallas=False)
    assert _rel_err(out, want) < 1e-5


def test_swa_bf16():
    b, h, s, d = 1, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.bfloat16)
    out = ops.swa_attention(q, k, v, window=77, use_pallas=True,
                            interpret=True, block_q=64, block_kv=64)
    want = ops.swa_attention(q, k, v, window=77, use_pallas=False)
    assert _rel_err(out, want) < 3e-2


# ---------------------------------------------------------------------------
# elastic tiling (the generalized eq. 19)
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(m=st.integers(1, 8192), k=st.integers(1, 8192), n=st.integers(1, 8192))
def test_elastic_tiles_properties(m, k, n):
    cfg = elastic.choose_tiles(m, k, n)
    assert 0 < cfg.utilization <= 1.0
    assert cfg.vmem_bytes <= elastic.VMEM_BUDGET
    assert cfg.bm % elastic.SUBLANE == 0
    assert cfg.bn % elastic.MXU_DIM == 0
    if cfg.schedule == "weight_stationary":
        assert cfg.bk >= k  # full-K residency (padded up)


def test_elastic_prefers_weight_stationary_when_it_fits():
    cfg = elastic.choose_tiles(4096, 4096, 4096, in_bytes=2)
    assert cfg.schedule == "weight_stationary"
    # weight traffic is then K*N once (Kraken's rotation), beating
    # output-stationary re-reads.
    os_words = elastic.modeled_hbm_words(4096, 4096, 4096, cfg.bm, 512,
                                         cfg.bn, "output_stationary")
    assert cfg.hbm_words < os_words


def test_tile_utilization_exact():
    assert elastic.tile_utilization(256, 256, 256, 128, 128, 128) == 1.0
    assert elastic.tile_utilization(129, 128, 128, 128, 128, 128) == pytest.approx(129 / 256)


# ---------------------------------------------------------------------------
# candidate enumeration (the autotuner's search space)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 8192), k=st.integers(1, 8192), n=st.integers(1, 8192))
def test_enumerate_tiles_invariants(m, k, n):
    cands = elastic.enumerate_tiles(m, k, n)
    assert cands, "candidate list must never be empty"
    assert len({(c.bm, c.bk, c.bn, c.schedule) for c in cands}) == len(cands)
    for c in cands:
        assert c.schedule in ("weight_stationary", "output_stationary")
        assert 0 < c.utilization <= 1.0
        if c.schedule == "weight_stationary":
            assert c.bk >= k  # full-K residency (padded up)
    # choose_tiles is exactly the model-best of the enumeration.
    assert elastic.model_best(cands) == elastic.choose_tiles(m, k, n,
                                                             mode="model")


@settings(max_examples=5, deadline=None)
@given(m=st.integers(1, 96), k=st.integers(1, 96), n=st.integers(1, 96))
def test_kraken_gemm_parity_over_enumerated_candidates(m, k, n):
    """Every candidate the autotuner may time must be numerically correct
    under both schedules (interpret-mode kraken_gemm vs the ref oracle)."""
    from repro.tuning import search
    rng = np.random.default_rng(m * 131 + k * 7 + n)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    want = ref.matmul(a, b)
    cands = elastic.enumerate_tiles(m, k, n, in_bytes=4)
    assert {c.schedule for c in cands} == {"weight_stationary",
                                           "output_stationary"}
    for cfg in cands:
        got = search.run_gemm_candidate(a, b, cfg, interpret=True)
        assert _rel_err(got, want) < 1e-5, (cfg, m, k, n)