"""Logical-axis sharding: rules map model-level axis names ("embed", "mlp",
"batch", ...) onto physical mesh axes ("pod", "data", "model").

Models annotate parameters and activations with logical axes only; the
launcher picks a rule set (``DP_RULES`` for the paper's compressed
data-parallel mode, ``FSDP_RULES`` for the GSPMD baseline), optionally
extends it across pods with ``with_pod``, and ``resolve_spec`` turns
(shape, logical axes) into a ``PartitionSpec`` — dropping assignments that
don't divide the dimension and never using a mesh axis twice.

``activation_sharding`` makes a rule set ambient so model code can call
``logical_constraint`` without threading rules/mesh through every layer.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _as_tuple(v) -> tuple[str, ...]:
    """Normalize a rules entry: None -> (), "model" -> ("model",)."""
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(a for a in v if a is not None)


# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

# Compressed data-parallel mode (Algorithm 1): parameters replicated over the
# data axis (each replica holds the full model slice and exchanges sparse
# gradient messages); tensor-parallel dims go to "model".
DP_RULES: dict[str, Any] = {
    # activations
    "batch": ("data",),
    "seq": None,
    # dense transformer params
    "embed": None,
    "vocab": ("model",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    # MoE
    "experts": ("data",),
    "expert_mlp": ("model",),
    # MLA / low-rank adapters (deepseek, rwkv time-mix)
    "mla": None,
    "mla_dense": ("model",),
    "kv_lora": ("model",),
    "qk_rope": ("model",),
    "lora_a": None,
    "lora_b": ("model",),
    "w_lora_a": None,
    "w_lora_b": ("model",),
    # SSM / RWKV
    "conv": None,
    "state": None,
    "rwkv": None,
    # scan-over-layers stacks are never sharded along the layer axis
    "layers": None,
}

# GSPMD baseline (fsdp): like DP but parameter "embed" dims shard over the
# data axis (ZeRO-3-style weight sharding; XLA inserts the gathers).
FSDP_RULES: dict[str, Any] = dict(DP_RULES, embed=("data",))


def with_pod(rules: dict) -> dict:
    """Extend a rule set onto a ("pod", "data", "model") mesh: every use of
    the "data" axis is widened to span pods as well."""
    out = {}
    for k, v in rules.items():
        axes = _as_tuple(v)
        if "data" in axes:
            widened = []
            for a in axes:
                if a == "data":
                    widened += ["pod", "data"]
                else:
                    widened.append(a)
            out[k] = tuple(widened)
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Spec resolution
# ---------------------------------------------------------------------------

def _mesh_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def resolve_spec(shape, axes, rules: dict, mesh) -> P:
    """(dim sizes, logical axes) -> PartitionSpec under ``rules`` on ``mesh``.

    Per dimension: look the logical axis up in the rules, keep only mesh axes
    that exist and are not already used by an earlier dimension, and drop the
    whole assignment unless the dimension size divides evenly.
    """
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    entries: list[Any] = []
    axes = tuple(axes) if axes is not None else ()
    for i, dim in enumerate(tuple(shape)):
        logical = axes[i] if i < len(axes) else None
        names = [a for a in _as_tuple(rules.get(logical) if logical else None)
                 if a in sizes and a not in used]
        prod = 1
        for a in names:
            prod *= sizes[a]
        if not names or prod <= 1 or dim % prod != 0:
            entries.append(None)
            continue
        used.update(names)
        entries.append(names[0] if len(names) == 1 else tuple(names))
    return P(*entries)


def tree_shardings(vals: Any, axes: Any, rules: dict, mesh) -> Any:
    """Map (value tree, logical-axes tree) -> NamedSharding tree."""
    def _is_axes(t):
        return isinstance(t, tuple) and all(isinstance(e, (str, type(None)))
                                            for e in t)
    return jax.tree.map(
        lambda v, ax: NamedSharding(mesh, resolve_spec(v.shape, ax, rules,
                                                       mesh)),
        vals, axes,
        is_leaf=lambda t: _is_axes(t) or hasattr(t, "shape"))


# ---------------------------------------------------------------------------
# Ambient activation rules
# ---------------------------------------------------------------------------

_ACTIVE = threading.local()


def _rule_stack() -> list:
    if not hasattr(_ACTIVE, "stack"):
        _ACTIVE.stack = []
    return _ACTIVE.stack


@contextlib.contextmanager
def activation_sharding(rules: dict, mesh):
    """Make (rules, mesh) ambient for ``logical_constraint`` in this thread."""
    _rule_stack().append((rules, mesh))
    try:
        yield
    finally:
        _rule_stack().pop()


def _in_manual_region() -> bool:
    """True while tracing inside a shard_map/pmap body. The ambient rules
    hold a concrete full mesh, which a manual region cannot re-constrain
    onto, so ``logical_constraint`` is the identity there (the constraint
    is only a layout hint)."""
    return bool(jax.core.nonempty_axis_env_DO_NOT_USE())


def logical_constraint(x: jax.Array, axes) -> jax.Array:
    """Sharding hint on an activation via the ambient rules; identity when no
    ``activation_sharding`` context is active or nothing resolves."""
    stack = _rule_stack()
    if not stack:
        return x
    rules, mesh = stack[-1]
    if mesh is None or _in_manual_region():
        return x
    spec = resolve_spec(x.shape, axes, rules, mesh)
    if all(e is None for e in tuple(spec)):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
