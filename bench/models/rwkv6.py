"""RWKV-6 ("Finch") as the serving program computes it, as a float32
reference with per-token recurrence, and the work counts.

Per layer: LayerNorm, time mixing (token shift, data-dependent decay
``w_t = exp(-exp(w0 + tanh(x_w A) B))``, per-head state
``S_t = diag(w_t) S_{t-1} + k_t v_t^T``, output
``r_t (S_{t-1} + diag(u) k_t v_t^T)``, per-head group norm, SiLU gate),
then LayerNorm and channel mixing (``sigmoid(x_r W_r) * (relu(x_k W_k)^2
W_v)``).  Where the program departs from the published Finch block (the
gate reads the unshifted input, the token shift is a static lerp, no ``ln0``
after the embedding, no bias on the group norm, a decay LoRA of rank
``max(32, d/16)``), this reference follows the program; ``PERF.md`` lists
the departures.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.models.common import HIGHEST, layer_norm, matmul

GROUP_NORM_EPS = 64e-5     # RWKV-6's ln_x: 1e-5 * head_size_divisor**2


def dims(c):
    d = c["hidden_size"]
    return dict(n_layers=c["num_hidden_layers"], d=d,
                heads=d // c["head_size"], dh=c["head_size"],
                f=c["intermediate_size"], vocab=c["vocab_size"],
                lora=max(32, d // 16))


def layer_shapes(c) -> dict:
    m = dims(c)
    d, f, lora = m["d"], m["f"], m["lora"]
    s = {"attn_norm_gamma": (d,), "attn_norm_beta": (d,),
         "mlp_norm_gamma": (d,), "mlp_norm_beta": (d,)}
    for n in ("mix_r", "mix_k", "mix_v", "mix_w", "w0", "bonus", "ln_gamma"):
        s[f"rwkv_{n}"] = (d,)
    for n in ("wr", "wk", "wv", "wg", "wo"):
        s[f"rwkv_{n}"] = (d, d)
    s["rwkv_wa"], s["rwkv_wb"] = (d, lora), (lora, d)
    s.update({"cmix_mix_k": (d,), "cmix_mix_r": (d,), "cmix_wk": (d, f),
              "cmix_wv": (f, d), "cmix_wr": (d, d)})
    return s


def global_shapes(c) -> dict:
    m = dims(c)
    return {"embed": (m["vocab"], m["d"]), "final_norm_gamma": (m["d"],),
            "final_norm_beta": (m["d"],), "unembed": (m["d"], m["vocab"])}


def _decay_speed(layer, n_layers, shape):
    """RWKV-6's initial ``time_decay``: -6 + 5 (n / (d-1))^(0.7 + 1.3 r),
    r = layer / (n_layers - 1): per-step decay between exp(-e^-6) and
    exp(-e^-1)."""
    d = shape[0]
    r = layer / max(1, n_layers - 1)
    n = np.arange(d, dtype=np.float64) / (d - 1)
    return (-6.0 + 5.0 * n ** (0.7 + 1.3 * r)).astype(np.float32)


def _bonus(layer, n_layers, shape):
    """RWKV-6's initial ``time_faaaa``: r (1 - n/(d-1)) + zigzag."""
    d = shape[0]
    r = layer / max(1, n_layers - 1)
    n = np.arange(d, dtype=np.float64)
    return (r * (1.0 - n / (d - 1)) + ((n + 1) % 3 - 1) * 0.1).astype(
        np.float32)


def rules(c) -> dict:
    r = {n: ("normal", 1.0) for n in list(layer_shapes(c))
         + list(global_shapes(c))}
    r.update({n: ("const", 1.0) for n in r if n.endswith("_gamma")})
    r.update({n: ("const", 0.0) for n in r if n.endswith("_beta")})
    r.update({n: ("uniform", 0.0, 1.0) for n in r if "_mix_" in n})
    r["rwkv_w0"] = ("fn", _decay_speed)
    r["rwkv_bonus"] = ("fn", _bonus)
    r["rwkv_wa"] = r["rwkv_wb"] = ("normal", 0.1)
    r["embed"] = ("std", 1.0)
    return r


# ---------------------------------------------------------------- work

def layer_matmuls(c) -> list:
    return [s for n, s in layer_shapes(c).items() if len(s) == 2]


def head_matmul(c):
    return global_shapes(c)["unembed"]


def mixer_flops(c, ctx: int) -> float:
    """Recurrence FLOPs of one token in one layer, whatever the context:
    per head ``r.S`` (2 dh^2), ``w*S + k v^T`` (3 dh^2) and the bonus
    term (2 dh^2 + dh, counted as 2 dh^2)."""
    m = dims(c)
    return 7.0 * m["heads"] * m["dh"] ** 2


def kv_bytes_per_token(c, itemsize: int = 2) -> float:
    return 0.0


# ---------------------------------------------------------- reference

def _shift(h):
    """Token shift within each sequence: the previous token, zero first."""
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """Per-token recurrence.  r/k/v/w [N, L, H, D], u [H, D] -> [N, L, H, D]."""
    n, L, h, d = r.shape

    def tok(s, t):
        rt, kt, vt, wt = t                                  # [N, H, D]
        kv = kt[..., :, None] * vt[..., None, :]            # [N, H, D, D]
        y = jnp.einsum("nhd,nhde->nhe", rt, s + u[None, :, :, None] * kv,
                       precision=HIGHEST)
        return wt[..., :, None] * s + kv, y

    s0 = jnp.zeros((n, h, d, d), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, ys = jax.lax.scan(tok, s0, xs)
    return jnp.moveaxis(ys, 0, 1)


def _layer(c, quant, x, w):
    m = dims(c)
    n, L, d = x.shape
    H, D, eps = m["heads"], m["dh"], c["layer_norm_epsilon"]
    h = layer_norm(x, w["attn_norm_gamma"], w["attn_norm_beta"], eps)
    hs = _shift(h)
    mix = lambda nm: h + (hs - h) * w[f"rwkv_mix_{nm}"]
    r = matmul(mix("r"), w["rwkv_wr"], quant)
    k = matmul(mix("k"), w["rwkv_wk"], quant)
    v = matmul(mix("v"), w["rwkv_wv"], quant)
    g = jax.nn.silu(matmul(h, w["rwkv_wg"], quant))
    lora = matmul(jnp.tanh(matmul(mix("w"), w["rwkv_wa"], quant)),
                  w["rwkv_wb"], quant)
    decay = jnp.exp(-jnp.exp(w["rwkv_w0"] + lora))
    heads = lambda a: a.reshape(n, L, H, D)
    y = _wkv(heads(r), heads(k), heads(v), heads(decay),
             w["rwkv_bonus"].reshape(H, D))
    mu = y.mean(-1, keepdims=True)
    var = ((y - mu) ** 2).mean(-1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + GROUP_NORM_EPS)).reshape(n, L, d)
    x = x + matmul(y * w["rwkv_ln_gamma"] * g, w["rwkv_wo"], quant)
    h = layer_norm(x, w["mlp_norm_gamma"], w["mlp_norm_beta"], eps)
    hs = _shift(h)
    xk = h + (hs - h) * w["cmix_mix_k"]
    xr = h + (hs - h) * w["cmix_mix_r"]
    kk = jnp.square(jax.nn.relu(matmul(xk, w["cmix_wk"], quant)))
    rr = jax.nn.sigmoid(matmul(xr, w["cmix_wr"], quant))
    return x + rr * matmul(kk, w["cmix_wv"], quant)


def logits_at(c, seed: int, tokens, check, quant: str | None = None,
              dtype=jnp.bfloat16) -> np.ndarray:
    """As :func:`bench.models.llama.logits_at`."""
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
    hfin = layer_norm(hfin, glob["final_norm_gamma"],
                      glob["final_norm_beta"], c["layer_norm_epsilon"])
    return np.asarray(jax.jit(functools.partial(matmul, quant=quant))(
        hfin, glob["unembed"]))
