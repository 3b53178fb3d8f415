"""Seeded model weights, made on the device by name.

Every weight is a pure function of (seed, leaf name, layer index): the
serving program's parameter tree is built from it in one jitted call, and
the float32 references in ``bench/models`` make any single layer again from
the same (seed, name, layer), without touching what the program holds.

A family module (``bench/models/<family>.py``) gives the rule for each leaf
name: ``("normal", scale)`` is a normal draw times ``scale / sqrt(fan_in)``,
``("std", s)`` a normal draw times ``s``, ``("uniform", lo, hi)``, ``("const", value)``, or ``("fn", f)`` with
``f(layer, n_layers, shape) -> float32 array`` for deterministic tables.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size: the low 32 bits seed it, the rest are
    folded in, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key, name: str, layer):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer + 1)


def draw(key, name: str, layer, shape, rule, n_layers: int) -> jax.Array:
    """One layer's float32 value of leaf ``name`` (``layer`` = -1 for a
    leaf outside the layer stack; it may be traced)."""
    kind = rule[0]
    if kind == "normal":
        fan_in = shape[0] if len(shape) == 1 else shape[-2]
        k = _leaf_key(key, name, layer)
        return jax.random.normal(k, shape, jnp.float32) * (
            rule[1] / jnp.sqrt(jnp.float32(fan_in)))
    if kind == "std":
        return jax.random.normal(_leaf_key(key, name, layer), shape,
                                 jnp.float32) * rule[1]
    if kind == "uniform":
        k = _leaf_key(key, name, layer)
        return jax.random.uniform(k, shape, jnp.float32, rule[1], rule[2])
    if kind == "const":
        return jnp.full(shape, rule[1], jnp.float32)
    raise ValueError(f"unknown weight rule {rule!r} for {name}")


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def program_params(specs, rules: dict, seed: int, n_layers: int, dtype):
    """The serving program's parameter tree, in ``dtype``, on the default
    device, from one jitted call.  ``specs`` is the program's own tree of
    ``ShapeDtypeStruct``; leaves whose leading axis is ``n_layers`` inside
    ``stack`` are stacked layers, drawn layer by layer."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def make(key):
        leaves = []
        for path, spec in flat:
            name = leaf_name(path)
            if name not in rules:
                raise KeyError(f"no weight rule for program leaf {name!r}")
            stacked = any(getattr(p, "key", None) == "stack" for p in path)
            if rules[name][0] == "fn":
                f = rules[name][1]
                val = jnp.asarray(np.stack([f(i, n_layers, spec.shape[1:])
                                            for i in range(spec.shape[0])])
                                  if stacked else f(-1, n_layers, spec.shape))
            elif stacked:
                per = tuple(spec.shape[1:])
                layers = jnp.arange(spec.shape[0])
                val = jax.vmap(lambda i: draw(key, name, i, per, rules[name],
                                              n_layers))(layers)
            else:
                val = draw(key, name, -1, tuple(spec.shape), rules[name],
                           n_layers)
            leaves.append(val.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(base_key(seed))


def layer_maker(names_shapes: dict, rules: dict, seed: int, n_layers: int,
                dtype):
    """``make(layer)``: one layer's weights as the program holds them
    (rounded to ``dtype``), in float32 — what a reference computes with.
    ``layer`` = -1 for the leaves outside the layer stack."""
    drawn = {n: s for n, s in names_shapes.items() if rules[n][0] != "fn"}

    @jax.jit
    def random_part(layer):
        key = base_key(seed)
        return {n: draw(key, n, layer, s, rules[n], n_layers)
                .astype(dtype).astype(jnp.float32) for n, s in drawn.items()}

    def make(layer: int) -> dict:
        out = dict(random_part(layer))
        for n, s in names_shapes.items():
            if n not in drawn:
                out[n] = jnp.asarray(rules[n][1](layer, n_layers, s)) \
                    .astype(dtype).astype(jnp.float32)
        return out

    return make
