"""Shared building blocks: norms, rotary embeddings, MLPs, embedding table."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical_constraint
from repro.models.common import Initializer


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(ini: Initializer, dim: int):
    return {"scale": ini.zeros((dim,), ("embed",))}    # gemma-style (1+scale)


def rmsnorm(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def init_layernorm(ini: Initializer, dim: int):
    return {"scale": ini.ones((dim,), ("embed",)),
            "bias": ini.zeros((dim,), ("embed",))}


def layernorm(p, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + eps)
    out = normed * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


NORMS = {"rms": (init_rmsnorm, rmsnorm), "layer": (init_layernorm, layernorm)}


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_softmax_scale(scaling) -> float:
    """Factor on the attention softmax scale under YaRN (``scaling`` as
    ``(key, value)`` pairs of a published ``rope_scaling``): the square of
    ``mscale(mscale_all_dim)``; 1 without scaling."""
    if scaling is None:
        return 1.0
    y = dict(scaling)
    m_all = y.get("mscale_all_dim", 0)
    return yarn_mscale(y["factor"], m_all) ** 2 if m_all else 1.0


def _yarn(scaling, freq, head_dim: int, theta: float):
    """YaRN (arXiv:2309.00071) as DeepSeek-V2 publishes it: frequencies
    blended between the plain and the ``factor``-interpolated ones over the
    ramp between ``beta_fast`` and ``beta_slow`` rotations in the original
    context, and the cos/sin scale ``mscale(mscale) /
    mscale(mscale_all_dim)``."""
    y = dict(scaling)
    if y.get("type", y.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {y!r}: only YaRN is implemented")
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def corr_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp                 # where the plain frequency is kept
    freq = freq / factor * (1.0 - extra) + freq * extra
    mscale = (yarn_mscale(factor, y.get("mscale", 1))
              / yarn_mscale(factor, y.get("mscale_all_dim", 0)))
    return freq, mscale


def rope_table(positions: jax.Array, head_dim: int, theta: float = 10000.0,
               scaling=None):
    """positions [*, S] -> (sin, cos) of shape [*, S, head_dim/2], fp32;
    YaRN frequencies and cos/sin scale under ``scaling`` (pairs)."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    mscale = 1.0
    if scaling is not None:
        freq, mscale = _yarn(scaling, freq, head_dim, theta)
    angles = positions.astype(jnp.float32)[..., None] * freq
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    return sin, cos


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [..., S, H, D] with (sin, cos) [..., S, D/2] (broadcast over heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s, c = sin[..., None, :], cos[..., None, :]      # add head axis
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_gated_mlp(ini: Initializer, d_model: int, d_ff: int):
    return {"gate": ini.fan_in((d_model, d_ff), ("embed", "mlp")),
            "up": ini.fan_in((d_model, d_ff), ("embed", "mlp")),
            "down": ini.fan_in((d_ff, d_model), ("mlp", "embed"))}


def gated_mlp(p, x, act: str = "gelu"):
    """GeGLU (gemma) / SwiGLU (llama-family)."""
    fn = jax.nn.gelu if act == "gelu" else jax.nn.silu
    h = fn(x @ p["gate"]) * (x @ p["up"])
    h = logical_constraint(h, ("batch", "seq", "mlp"))
    return h @ p["down"]


def init_dense_mlp(ini: Initializer, d_model: int, d_ff: int):
    return {"up": ini.fan_in((d_model, d_ff), ("embed", "mlp")),
            "up_b": ini.zeros((d_ff,), ("mlp",)),
            "down": ini.fan_in((d_ff, d_model), ("mlp", "embed")),
            "down_b": ini.zeros((d_model,), ("embed",))}


def dense_mlp(p, x, act: str = "gelu"):
    fn = jax.nn.gelu if act == "gelu" else jax.nn.silu
    h = fn(x @ p["up"] + p["up_b"])
    h = logical_constraint(h, ("batch", "seq", "mlp"))
    return h @ p["down"] + p["down_b"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(ini: Initializer, vocab: int, d_model: int):
    return {"table": ini.normal((vocab, d_model), ("vocab", "embed"), stddev=1.0)}


def embed(p, tokens: jax.Array, scale: bool = False) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0)
    if scale:                                   # gemma scales by sqrt(d_model)
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def unembed(p, x: jax.Array) -> jax.Array:
    """Tied unembedding: logits = x @ table^T."""
    return x @ p["table"].T


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap
