"""Weights made from the seed, the same for the program and the reference.

A configuration file's ``init`` section gives, per leaf path (``"/"``-joined
tree keys, e.g. ``blocks/b0_attn_sw/attn/wq``), the distribution of the
leaf: the first rule whose regular expression matches the path wins, else
``default``. Each leaf draws from a key folded from the path, so its values
do not depend on the order or the number of the other leaves."""
from __future__ import annotations

import re
import zlib

import jax
import jax.numpy as jnp


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def rule_for(init: dict, path: str) -> list:
    for pattern, *rule in init["rules"]:
        if re.search(pattern, path):
            return rule
    return init["default"]


def draw(rule: list, key: jax.Array, shape: tuple, dtype) -> jax.Array:
    kind, *args = rule
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32)
                * args[0]).astype(dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, args[0],
                                  args[1]).astype(dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"unknown init rule {rule!r}")


def make(init: dict, key: jax.Array, shapes: dict, dtype) -> dict:
    """``{path: array}`` for ``shapes = {path: shape}``. Call under ``jit``:
    every leaf is then made on the device in one program."""
    return {path: draw(rule_for(init, path), leaf_key(key, path),
                       tuple(shape), dtype)
            for path, shape in shapes.items()}


def paths_of(tree) -> list[str]:
    """Leaf paths of a nested-dict tree in ``jax.tree`` flatten order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(k.key) for k in p) for p, _ in flat]
