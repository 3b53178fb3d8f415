"""Distribution tests on a forced 8-device host mesh (subprocess: device
count must be set before jax initializes).  Covers sharded train-step
lowering, logical-rule application, elastic re-sharding across meshes, and
the loop-aware HLO walker."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_train_step_lowers_on_8dev_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp, dataclasses, json
        from repro.configs import get_arch, smoke_config
        from repro.models.model import Model
        from repro.optim.adamw import AdamW
        from repro.launch import steps as S
        from repro import sharding as Sh
        from repro.launch.mesh import make_host_mesh
        from repro.roofline import hlo_walk

        cfg = smoke_config(get_arch('yi-6b'))
        mesh = make_host_mesh(2, 4)
        rules = dict(Sh.RULES_SINGLE_POD)
        model = Model(cfg)
        opt = AdamW()
        with Sh.use_mesh_and_rules(mesh, rules):
            ps = S.sharded_param_specs(model, mesh, rules)
            os_ = S.sharded_opt_specs(model, opt, mesh, rules)
            from repro.configs.base import ShapeCell
            cell = ShapeCell('t', 64, 8, 'train')
            bs = S.batch_specs(cfg, cell, mesh, rules)
            step = S.make_train_step(model, opt, num_microbatches=2)
            lowered = jax.jit(step).lower(ps, os_, bs)
            compiled = lowered.compile()
        txt = compiled.as_text()
        comps, entry = hlo_walk.parse_module(txt)
        w = hlo_walk.walk(comps, entry)
        print(json.dumps({
            'colls': {k: v for k, v in w.coll_counts.items()},
            'flops': w.dot_flops,
            'levels': w.n_while_levels,
        }))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    # DP gradient sync must exist, and the scan structure must be visible.
    assert sum(rec["colls"].values()) > 0
    assert rec["flops"] > 0
    assert rec["levels"] >= 2  # microbatch loop + layer scan


def test_elastic_reshard_across_meshes():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json, tempfile
        from repro.configs import get_arch, smoke_config
        from repro.models.model import Model
        from repro.checkpoint import checkpoint as ckpt
        from repro.checkpoint.elastic import elastic_restore
        from repro.launch.mesh import make_host_mesh
        from repro import sharding as Sh

        cfg = smoke_config(get_arch('yi-6b'))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        d = tempfile.mkdtemp()
        ckpt.save(d, 3, params)

        # restore onto a (4, 2) mesh -- a different topology than training
        mesh = make_host_mesh(4, 2)
        rules = dict(Sh.RULES_SINGLE_POD)
        axes = model.param_axes()
        restored, step, _ = elastic_restore(d, model.param_specs(), axes,
                                            mesh, rules)
        ok = True
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            ok &= bool(jnp.allclose(a.astype(jnp.float32),
                                    b.astype(jnp.float32), atol=1e-6))
        n_sharded = sum(
            1 for l in jax.tree.leaves(restored)
            if len(getattr(l.sharding, 'device_set', [])) == 8)
        print(json.dumps({'ok': ok, 'step': step, 'n_sharded': n_sharded}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"] and rec["step"] == 3
    assert rec["n_sharded"] > 0


def test_compressed_allreduce_under_shard_map():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json, functools
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((8,), ('pod',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from repro.optim import compress
        g = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) / 7.0
        state = compress.init_state({'w': g[0]})

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P('pod'),),
                           out_specs=P('pod'), check_vma=False)
        def sync(local_g):
            grads = {'w': local_g[0]}
            st = compress.init_state(grads)
            mean, _ = compress.allreduce_compressed(grads, st, 'pod')
            return mean['w'][None]

        out = sync(g)
        want = g.mean(0)
        err = float(jnp.abs(out[0] - want).max())
        print(json.dumps({'err': err, 'scale_bound': float(jnp.abs(g).max()) / 127}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["err"] <= rec["scale_bound"] * 1.5 + 1e-6


def test_dryrun_cell_on_host_mesh():
    """A miniature dry-run: lower a serving cell with a 2x4 mesh."""
    out = run_py("""
        import jax, jax.numpy as jnp, json, dataclasses
        from repro.configs import get_arch, smoke_config
        from repro.configs.base import ShapeCell
        from repro.models.model import Model
        from repro.launch import steps as S
        from repro.launch.mesh import make_host_mesh
        from repro import sharding as Sh

        cfg = smoke_config(get_arch('mixtral-8x22b'))
        mesh = make_host_mesh(2, 4)
        rules = dict(Sh.RULES_SINGLE_POD, kv_seq=('model',))
        model = Model(cfg)
        with Sh.use_mesh_and_rules(mesh, rules):
            ps = S.sharded_param_specs(model, mesh, rules)
            cs = S.sharded_cache_specs(model, 8, 64, mesh, rules)
            tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((8,), jnp.int32)
            step = S.make_decode_step(model)
            compiled = jax.jit(step).lower(ps, cs, tok, pos).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({'arg_b': mem.argument_size_in_bytes,
                          'ok': True}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"] and rec["arg_b"] > 0

def test_context_parallel_attention_matches_plain():
    """shard_map context-parallel attention (heads indivisible by the model
    axis — the llama4/llama-3.2 case) must match the plain chunked path in
    forward AND gradient (§Perf bonus cell)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import sharding as Sh
        from repro.models import layers as L

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules = dict(Sh.RULES_SINGLE_POD, attn_context_parallel="model")
        rng = np.random.default_rng(0)
        B, H, KV, S, D = 2, 6, 2, 4096, 16   # H=6 % model=4 != 0
        q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
        pos = jnp.arange(S)

        def cp(q, k, v, w=0):
            with Sh.use_mesh_and_rules(mesh, rules):
                return L._gqa_sdpa(q, k, v, mask_mode="causal", window=w,
                                   q_pos=pos, kv_pos=pos)

        def plain(q, k, v, w=0):
            return L._gqa_sdpa_chunked(q, k, v, window=w, q_pos=pos,
                                       kv_pos=pos, causal=True)

        fwd = float(jnp.abs(jax.jit(cp)(q, k, v)
                            - jax.jit(plain)(q, k, v)).max())
        g1 = jax.grad(lambda q_: jnp.sum(jnp.tanh(cp(q_, k, v))))(q)
        g2 = jax.grad(lambda q_: jnp.sum(jnp.tanh(plain(q_, k, v))))(q)
        grad = float(jnp.abs(g1 - g2).max())
        win = float(jnp.abs(jax.jit(lambda a, b, c: cp(a, b, c, 512))(q, k, v)
                            - plain(q, k, v, 512)).max())
        print(json.dumps({"fwd": fwd, "grad": grad, "win": win}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["fwd"] < 1e-5 and rec["grad"] < 1e-5 and rec["win"] < 1e-5
