"""Logical-axis sharding: rules map logical array axes -> mesh axes.

Model code never names mesh axes; it annotates values with *logical* axes
("batch", "seq", "heads", "mlp", "experts", ...) via `constrain`.  A rules
context binds logical -> physical for the current mesh, with automatic
divisibility fallback: a logical axis whose dimension does not divide its
mesh-axis product is silently left unsharded (e.g. hymba's 25 heads on a
16-way model axis) — the 2D layouts keep working across all ten assigned
architectures without per-arch special cases.

Default rule set (the baseline the §Perf iterations start from):

    batch    -> ("pod", "data")     activations / env fleet
    embed    -> "data"              FSDP on the weight's d_model axis
    heads    -> "model"             attention-head parallel
    kv_heads -> "model"
    mlp      -> "model"             FFN hidden tensor-parallel
    experts  -> "model"             expert parallel
    vocab    -> "model"             embedding/logit shard
    seq      -> None                (sequence parallel is a §Perf change)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "seq": None,
    "kv_seq": "model",   # decode KV caches: sequence-shard over `model`
    "act_seq": "model",  # stored residual stream (Megatron-style SP)
    "state": None,
}


class AxisRules:
    def __init__(self, mesh: Mesh | None, rules: dict[str, Any] | None = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def mesh_axes(self, logical: str | None):
        if logical is None:
            return None
        return self.rules.get(logical)


_state = threading.local()


def current_rules() -> AxisRules | None:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh | None, rules: dict[str, Any] | None = None):
    """Bind logical->mesh rules for model code executed in this context.

    NOTE: the context must be live at TRACE time (jit tracing), which is the
    natural usage: `with mesh, axis_rules(mesh): jitted(...)`.
    """
    prev = current_rules()
    _state.rules = AxisRules(mesh, rules)
    try:
        yield _state.rules
    finally:
        _state.rules = prev


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return int(np.prod([mesh.shape[a] for a in axes]))


def logical_to_spec(shape: tuple[int, ...], logical: tuple[str | None, ...],
                    rules: AxisRules) -> P:
    """PartitionSpec for `shape` under `rules`, dropping non-divisible axes."""
    assert len(shape) == len(logical), (shape, logical)
    if rules.mesh is None:
        return P()
    spec = []
    used: set[str] = set()
    for dim, name in zip(shape, logical):
        axes = rules.mesh_axes(name)
        if axes is None:
            spec.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        # drop axes already consumed by an earlier dim of this array
        axes_t = tuple(a for a in axes_t if a not in used and a in rules.mesh.shape)
        if not axes_t or dim % _axis_size(rules.mesh, axes_t) != 0:
            spec.append(None)
            continue
        used.update(axes_t)
        spec.append(axes_t[0] if len(axes_t) == 1 else axes_t)
    return P(*spec)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a rules ctx."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = logical_to_spec(x.shape, logical, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def named_sharding(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def param_specs(params: Any, logical_axes: Any, rules: AxisRules) -> Any:
    """Pytree of PartitionSpec for a parameter pytree.

    `logical_axes` mirrors `params` with tuples of logical names per leaf
    (see models/*.py `param_axes`).  Leaves without an entry are replicated.
    """
    def is_axes_leaf(x):
        return x is None or (
            isinstance(x, tuple)
            and all(isinstance(s, str) or s is None for s in x)
        )

    flat_p, tdef = jax.tree.flatten(params)
    flat_ax = jax.tree.flatten(logical_axes, is_leaf=is_axes_leaf)[0]
    if len(flat_p) != len(flat_ax):
        raise ValueError(
            f"params has {len(flat_p)} leaves but logical_axes {len(flat_ax)}")
    specs = [P() if ax is None else logical_to_spec(p.shape, ax, rules)
             for p, ax in zip(flat_p, flat_ax)]
    return jax.tree.unflatten(tdef, specs)
