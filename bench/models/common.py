"""Pieces shared by the float32 references: precision, norms, GEMMs in
float32 or in the int8 control, and the layer loop."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, w, quant: str | None = None):
    """``a [..., K] @ w [K, N]`` in float32 at full precision, or, for the
    control, as the int8 GEMM a lower-precision path would run: weights
    quantized per output column, activations per row, symmetric absmax,
    products summed exactly in int32."""
    if quant is None:
        return jnp.matmul(a, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(quant)
    sw = jnp.max(jnp.abs(w), axis=0) / 127.0
    sw = jnp.where(sw == 0, 1.0, sw)
    wq = jnp.round(w / sw).astype(jnp.int8)
    sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    sa = jnp.where(sa == 0, 1.0, sa)
    aq = jnp.round(a / sa).astype(jnp.int8)
    acc = jax.lax.dot_general(aq, wq, (((aq.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sw


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b
