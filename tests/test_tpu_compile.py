"""The main path's Pallas kernels, compiled at real widths for a described
TPU v5e.

Interpret mode runs a kernel's grid on the CPU but checks none of the
TPU's lowering rules: block tiling, Mosaic's supported ops, VMEM limits.
The TPU compiler is installed here and compiles for a chip that is
described, not attached, so these tests catch on the CPU what the chip
would refuse.  Nothing runs; each test asserts that the compiled program
holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.kraken_moe_gemm import grouped_moe_gemm
from repro.kernels.paged_attention import (default_pages_per_block,
                                           paged_decode_attention)
from repro.kernels.swa_attention import swa_attention
from repro.models.moe import expert_capacity
from repro.tuning.search import paged_decode_candidates

YI = get_arch("yi-6b")
MIXTRAL = get_arch("mixtral-8x22b")
SLOTS, CHUNK, PAGE_SIZE, MAX_LEN = 4, 256, 16, 2048   # chip_smoke's engine


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache, tmp_path_factory):
    from jax.experimental import topologies
    # the TPU library writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu")))
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK])
@pytest.mark.parametrize("k,n", [(YI.d_model, YI.d_model),
                                 (YI.d_model, YI.d_ff),
                                 (YI.d_ff, YI.d_model)])
def test_kraken_matmul_compiles(one_chip, m, k, n):
    """yi-6b projections at the engine's decode and mixed M."""
    def gemm(a, b):
        return ops.kraken_matmul(a, b, use_pallas=True, interpret=False,
                                 tile_mode="model")
    text = _compiled_text(gemm, one_chip, ((m, k), jnp.bfloat16),
                          ((k, n), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ppb", ["default", "largest"])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("page_size,max_len", [(8, 64), (PAGE_SIZE, MAX_LEN)])
@pytest.mark.parametrize("arch", [YI, MIXTRAL], ids=lambda a: a.name)
def test_paged_decode_compiles(one_chip, arch, page_size, max_len, quantized,
                               ppb):
    """yi-6b's GQA geometry (4 KV heads, group 8, head_dim 128) and
    mixtral-8x22b's (8 KV heads, group 6, its sliding window) on 8-token
    pages over 64 positions and on 16-token pages over 2048, at the default
    pages-per-block and at the largest one the autotuner may pick: a grid
    step holds every KV head of its pages, so wider KV geometries are where
    VMEM would refuse."""
    kvh, d = arch.num_kv_heads, arch.head_dim
    g = arch.num_heads // kvh
    mp = max_len // page_size
    n_pages = SLOTS * mp + 1
    pool = jnp.int8 if quantized else jnp.bfloat16
    geom = dict(kv_heads=kvh, head_dim=d, itemsize=jnp.dtype(pool).itemsize)
    pages_per_block = (default_pages_per_block(page_size, mp, **geom)
                       if ppb == "default"
                       else max(paged_decode_candidates(page_size, mp,
                                                        **geom)))
    shapes = [((SLOTS, kvh * g, d), jnp.bfloat16),
              ((n_pages, kvh, page_size, d), pool),
              ((n_pages, kvh, page_size, d), pool),
              ((n_pages, page_size), jnp.int32),
              ((SLOTS, mp), jnp.int32),
              ((SLOTS,), jnp.int32)]
    if quantized:
        shapes += [((n_pages, kvh, page_size), jnp.float32)] * 2

    def attend(q, k, v, pos, table, q_pos, k_scale=None, v_scale=None):
        return paged_decode_attention(
            q, k, v, pos_pages=pos, page_table=table, q_pos=q_pos,
            k_scale=k_scale, v_scale=v_scale, window=arch.sliding_window,
            pages_per_block=pages_per_block)
    assert "tpu_custom_call" in _compiled_text(attend, one_chip, *shapes)


@pytest.mark.parametrize("kernel", ["kraken_gemm", "paged_decode_attention"])
def test_main_path_kernels_carry_their_names(one_chip, kernel):
    """Each of the decode program's Pallas kernels is an HLO instruction
    named after it, so a profiler trace names its ops by kernel."""
    kvh, g, d = YI.num_kv_heads, YI.num_heads // YI.num_kv_heads, YI.head_dim
    mp = MAX_LEN // PAGE_SIZE
    n_pages = SLOTS * mp + 1
    if kernel == "kraken_gemm":
        def fn(a, b):
            return ops.kraken_matmul(a, b, use_pallas=True, interpret=False,
                                     tile_mode="model")
        shapes = [((SLOTS, YI.d_model), jnp.bfloat16),
                  ((YI.d_model, YI.d_ff), jnp.bfloat16)]
    else:
        def fn(q, k, v, pos, table, q_pos):
            return paged_decode_attention(q, k, v, pos_pages=pos,
                                          page_table=table, q_pos=q_pos)
        shapes = [((SLOTS, kvh * g, d), jnp.bfloat16),
                  ((n_pages, kvh, PAGE_SIZE, d), jnp.bfloat16),
                  ((n_pages, kvh, PAGE_SIZE, d), jnp.bfloat16),
                  ((n_pages, PAGE_SIZE), jnp.int32),
                  ((SLOTS, mp), jnp.int32), ((SLOTS,), jnp.int32)]
    calls = [line for line in _compiled_text(fn, one_chip, *shapes)
             .splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(", line), \
            line[:200]


@pytest.mark.parametrize("direction", ["up", "down"])
def test_grouped_moe_gemm_compiles(one_chip, direction):
    """mixtral-8x22b's expert bank (d 6144, f 16384, 8 experts) at the
    capacity of a mixed step of 4 slots x 256 tokens."""
    e, d, f = MIXTRAL.num_experts, MIXTRAL.d_model, MIXTRAL.d_ff
    k, n = (d, f) if direction == "up" else (f, d)
    cap = expert_capacity(SLOTS * CHUNK, MIXTRAL)
    text = _compiled_text(grouped_moe_gemm, one_chip,
                          ((e, cap, k), jnp.bfloat16),
                          ((e, k, n), jnp.bfloat16), ((e,), jnp.int32))
    assert "tpu_custom_call" in text


def test_swa_attention_compiles(one_chip):
    """mixtral-8x22b's sliding window (4096) over a 4096-token sequence."""
    h, kvh, d = MIXTRAL.num_heads, MIXTRAL.num_kv_heads, MIXTRAL.head_dim
    s, w = 4096, MIXTRAL.sliding_window

    def attend(q, k, v):
        return swa_attention(q, k, v, window=w)
    text = _compiled_text(attend, one_chip, ((1, h, s, d), jnp.bfloat16),
                          ((1, kvh, s, d), jnp.bfloat16),
                          ((1, kvh, s, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# Whole programs.  Code that asks jax.default_backend() takes its CPU branch
# here; these tests point it at the TPU so the model traces what the chip
# would run.
# ---------------------------------------------------------------------------

@pytest.fixture
def tpu_branches(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _placed(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_yi6b_decode_step_fits_one_chip(one_chip, tpu_branches):
    """The engine's decode step at yi-6b's published widths: every GEMM and
    the paged attention are kernels, and weights + pools + temporaries fit
    a 16 GB chip (unordered per-layer weight slices once took 5.45 GB of
    temporaries and did not)."""
    from repro.kernels.paged_attention import use_paged_decode_mode
    from repro.models.model import Model
    from repro.serving.state import build_state_tree
    model = Model(YI)
    state = build_state_tree(model, slots=SLOTS, page_size=PAGE_SIZE,
                             max_len=MAX_LEN)
    params = _placed(model.param_specs(), one_chip)
    pools = _placed(jax.eval_shape(state.init_device), one_chip)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)

    def decode(params, pools, tokens, pos, live):
        with use_paged_decode_mode("fused"):
            return model.decode_step(params, state.decode_view(pools, pos),
                                     tokens, pos, lengths=live)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pools, ints(SLOTS, 1), ints(SLOTS), ints(SLOTS)).compile()
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert kernels == YI.num_layers * 8 + 1   # 7 GEMMs + attention, unembed
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)])
def test_train_step_compiles(topo, one_chip, tpu_branches, mesh_shape):
    """The trainer's step on yi-6b widths (2 layers): on one chip the GEMMs
    are kernels differentiated through their custom VJP; on a 2x2 mesh
    they are XLA dots (a Mosaic kernel cannot be partitioned) and the
    gradients are all-reduced."""
    import dataclasses
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro import sharding as Sh
    from repro.configs.base import ShapeCell
    from repro.launch import steps as S
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    model = Model(dataclasses.replace(YI, num_layers=2))
    opt = AdamW()
    step = S.make_train_step(model, opt, remat="none")
    cell = ShapeCell("t", 512, 4, "train")
    if mesh_shape is None:
        mesh = rules = None
        args = (_placed(model.param_specs(), one_chip),
                _placed(opt.state_specs(model.param_specs()), one_chip),
                _placed(S.batch_specs(model.cfg, cell, None, None), one_chip))
    else:
        devices = np.array(topo.devices[:4]).reshape(mesh_shape)
        mesh = Mesh(devices, ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        rules = dict(Sh.RULES_SINGLE_POD)
        args = (S.sharded_param_specs(model, mesh, rules),
                S.sharded_opt_specs(model, opt, mesh, rules),
                S.batch_specs(model.cfg, cell, mesh, rules))
    with Sh.use_mesh_and_rules(mesh, rules):
        text = jax.jit(step).lower(*args).compile().as_text()
    if mesh is None:
        assert "tpu_custom_call" in text
    else:
        assert "tpu_custom_call" not in text and "all-reduce" in text
