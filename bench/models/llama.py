"""Dense decoder with grouped-query attention, RoPE, RMSNorm and SwiGLU
(the Llama layout that Yi-6B publishes), as a float32 reference, with the
work counts the per-layer metrics divide by.

Configuration keys are the published ``config.json`` names.  Weight names
are the serving program's leaf names; the values come from
``bench.weights`` by (seed, name, layer), never from the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.models.common import HIGHEST, matmul, rms_norm


def dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(n_layers=c["num_hidden_layers"], d=d, h=h,
                kv=c["num_key_value_heads"], hd=d // h,
                f=c["intermediate_size"], vocab=c["vocab_size"])


def layer_shapes(c) -> dict:
    m = dims(c)
    d, h, kv, hd, f = m["d"], m["h"], m["kv"], m["hd"], m["f"]
    return {"attn_norm_gamma": (d,), "attn_wq": (d, h * hd),
            "attn_wk": (d, kv * hd), "attn_wv": (d, kv * hd),
            "attn_wo": (h * hd, d), "mlp_norm_gamma": (d,),
            "mlp_wi_gate": (d, f), "mlp_wi_up": (d, f), "mlp_wo": (f, d)}


def global_shapes(c) -> dict:
    m = dims(c)
    return {"embed": (m["vocab"], m["d"]), "final_norm_gamma": (m["d"],),
            "unembed": (m["d"], m["vocab"])}


def rules(c) -> dict:
    """Normal weights at 1/sqrt(fan_in), unit norms, unit-variance
    embeddings."""
    r = {n: ("normal", 1.0) for n in list(layer_shapes(c))
         + list(global_shapes(c))}
    r.update({n: ("const", 1.0) for n in r if n.endswith("_gamma")})
    r["embed"] = ("std", 1.0)
    return r


# ---------------------------------------------------------------- work

def layer_matmuls(c) -> list:
    """(K, N) of every projection one token passes through in one layer."""
    return [s for n, s in layer_shapes(c).items() if len(s) == 2]


def head_matmul(c):
    return global_shapes(c)["unembed"]


def mixer_flops(c, ctx: int) -> float:
    """Attention FLOPs of one token in one layer that attends ``ctx`` keys
    (itself included): QK^T and PV."""
    m = dims(c)
    return 4.0 * m["h"] * m["hd"] * ctx


def kv_bytes_per_token(c, itemsize: int = 2) -> float:
    """K and V bytes one cached token holds, over all layers."""
    m = dims(c)
    return 2.0 * m["kv"] * m["hd"] * itemsize * m["n_layers"]


# ---------------------------------------------------------- reference

def _rope(x, theta):
    """x [N, L, H, D], positions 0..L-1 of each sequence."""
    L, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """One sequence: q [L, H, D], k/v [L, KV, D], causal."""
    L, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(L, kvh, h // kvh, d)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    return o.reshape(L, h * d)


def _layer(c, quant, x, w):
    m = dims(c)
    n, L, d = x.shape
    eps = c["rms_norm_eps"]
    hh = rms_norm(x, w["attn_norm_gamma"], eps)
    q = matmul(hh, w["attn_wq"], quant).reshape(n, L, m["h"], m["hd"])
    k = matmul(hh, w["attn_wk"], quant).reshape(n, L, m["kv"], m["hd"])
    v = matmul(hh, w["attn_wv"], quant).reshape(n, L, m["kv"], m["hd"])
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    o = jax.lax.map(lambda t: _attend(*t), (q, k, v))
    x = x + matmul(o, w["attn_wo"], quant)
    hh = rms_norm(x, w["mlp_norm_gamma"], eps)
    a = jax.nn.silu(matmul(hh, w["mlp_wi_gate"], quant)) \
        * matmul(hh, w["mlp_wi_up"], quant)
    return x + matmul(a, w["mlp_wo"], quant)


def logits_at(c, seed: int, tokens, check, quant: str | None = None,
              dtype=jnp.bfloat16) -> np.ndarray:
    """Float32 logits ``[P, vocab]`` after token ``pos`` of row ``i`` of
    ``tokens`` (``[N, L]`` ids) for each ``(i, pos)`` in ``check``, computed layer by layer with the
    weights rounded to ``dtype`` as served.  ``quant="int8"`` runs every
    GEMM as the int8 control."""
    m = dims(c)
    ru, n_layers = rules(c), m["n_layers"]
    glob = W.layer_maker(global_shapes(c), ru, seed, n_layers, dtype)(-1)
    x = jnp.take(glob.pop("embed"), jnp.asarray(tokens, jnp.int32), axis=0)
    make = W.layer_maker(layer_shapes(c), ru, seed, n_layers, dtype)
    step = jax.jit(functools.partial(_layer, c, quant), donate_argnums=(0,))
    for i in range(n_layers):
        x = step(x, make(i))
    idx = np.asarray(check, np.int32).reshape(-1, 2)
    hfin = x[idx[:, 0], idx[:, 1]]
    del x
    hfin = rms_norm(hfin, glob["final_norm_gamma"], c["rms_norm_eps"])
    return np.asarray(jax.jit(functools.partial(matmul, quant=quant))(
        hfin, glob["unembed"]))
