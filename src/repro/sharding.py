"""Logical-axis sharding: MaxText-style rules mapping logical axes to mesh axes.

Every parameter and key activation in the model code carries *logical* axis
names (``embed``, ``heads``, ``mlp``, ``experts``, ``vocab``, ``batch``,
``seq``, ...).  The launcher installs a mesh plus a rule table mapping
logical axes to mesh axes (DP/TP/EP/SP strategies are just different rule
tables), and the model code calls :func:`shard` /
:func:`logical_to_sharding` without knowing the physical topology.

Divisibility guard: a logical axis whose dimension is not divisible by the
product of its mapped mesh axes is silently replicated instead (recorded in
``dropped_axes`` so the roofline report can call it out) — this keeps every
(arch x mesh) cell compiling even for, e.g., 40 heads on a 16-way tensor
axis, at the cost of a known inefficiency that the perf loop can then fix.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_state = threading.local()

# Default rule tables.  Values are a mesh axis name, a tuple of them, or None.
RULES_SINGLE_POD = {
    "batch": ("data",),
    "moe_groups": ("data",),   # MoE dispatch groups ride the token sharding
    "seq": None,
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "qkv": ("model",),          # flattened heads*head_dim projections
    "mlp": ("model",),
    "experts": ("model",),
    "expert_capacity": None,
    "vocab": ("model",),
    "kv_seq": None,
    "layers": None,
    "conv_k": None,
    "state": None,
    "frontend_seq": None,
}

RULES_MULTI_POD = dict(RULES_SINGLE_POD, batch=("pod", "data"),
                       moe_groups=("pod", "data"))

# Sequence-parallel variants (long-context cells: batch too small to shard).
# moe_groups keeps riding the *token* sharding (flattened B*S = seq here).
RULES_SP_SINGLE_POD = dict(RULES_SINGLE_POD, batch=None, seq=("data",),
                           kv_seq=("data",), moe_groups=("data",))
RULES_SP_MULTI_POD = dict(RULES_SINGLE_POD, batch=None, seq=("pod", "data"),
                          kv_seq=("pod", "data"),
                          moe_groups=("pod", "data"))


def _ctx():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


@contextlib.contextmanager
def use_mesh_and_rules(mesh: Mesh | None, rules: dict[str, Any] | None):
    """Install (mesh, rules) for model code executed in this thread."""
    _ctx().append({"mesh": mesh, "rules": rules or {}, "dropped": []})
    try:
        yield
    finally:
        _ctx().pop()


def current() -> dict | None:
    stack = _ctx()
    return stack[-1] if stack else None


def partitioned() -> bool:
    """Whether model code is being traced under a multi-device mesh, where
    XLA partitions every op (and cannot partition a Pallas kernel)."""
    c = current()
    return c is not None and c["mesh"] is not None and c["mesh"].size > 1


def dropped_axes() -> list[tuple]:
    c = current()
    return list(c["dropped"]) if c else []


def _mesh_axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def partition_spec(shape: Sequence[int], logical_axes: Sequence[str | None]) -> PartitionSpec:
    """Map logical axes to a PartitionSpec under the installed rules."""
    c = current()
    if c is None or c["mesh"] is None:
        return PartitionSpec()
    mesh, rules = c["mesh"], c["rules"]
    spec = []
    used: set[str] = set()
    for dim, name in zip(shape, logical_axes):
        mapped = rules.get(name) if name else None
        if mapped is not None:
            if isinstance(mapped, str):
                mapped = (mapped,)
            # a mesh axis may appear at most once per spec: drop repeats
            mapped = tuple(a for a in mapped if a not in used)
            if not mapped:
                mapped = None
            else:
                size = _mesh_axis_size(mesh, mapped)
                if dim % size != 0:
                    c["dropped"].append((name, dim, mapped))
                    mapped = None
                else:
                    used.update(mapped)
        spec.append(mapped)
    # PartitionSpec wants strings or tuples.
    return PartitionSpec(*spec)


def logical_to_sharding(shape: Sequence[int], logical_axes: Sequence[str | None]):
    c = current()
    if c is None or c["mesh"] is None:
        return None
    return NamedSharding(c["mesh"], partition_spec(shape, logical_axes))


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Sharding constraint on an activation; no-op without an installed mesh."""
    c = current()
    if c is None or c["mesh"] is None:
        return x
    assert len(logical_axes) == x.ndim, (x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(c["mesh"], partition_spec(x.shape, logical_axes)))
