"""StarCoder2 (arXiv:2402.19173): LayerNorm, grouped-query attention with
RoPE and a sliding window, biases, GELU-tanh MLP."""
from __future__ import annotations

import math

import jax.numpy as jnp

from chipbench.reference import causal_attention, layernorm

MATMUL = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "ffn/up", "ffn/down")
PERIOD = ("attn_sw",)


def block(conf: dict, kind: str) -> dict:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    return {"attn/wq": (d, h, hd), "attn/wk": (d, kv, hd),
            "attn/wv": (d, kv, hd), "attn/wo": (h, hd, d),
            "attn/bq": (h, hd), "attn/bk": (kv, hd), "attn/bv": (kv, hd),
            "attn/bo": (d,), "ffn/up": (d, f), "ffn/up_b": (f,),
            "ffn/down": (f, d), "ffn/down_b": (d,),
            "ln1/scale": (d,), "ln1/bias": (d,), "ln2/scale": (d,),
            "ln2/bias": (d,)}


def program_sizes(cfg) -> dict:
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "sliding_window": cfg.window,
            "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab,
            "num_hidden_layers": cfg.num_layers, "use_bias": cfg.use_bias,
            "hidden_act": "gelu_tanh" if cfg.act == "gelu" else cfg.act,
            "tie_word_embeddings": True}


def mean_context(seq: int, window: int | None) -> float:
    """Mean number of keys a causal query of a ``seq`` row sees."""
    w = seq if window is None else min(window, seq)
    # position t sees min(t + 1, w) keys
    full = w * (w + 1) / 2 + (seq - w) * w
    return full / seq


def mixing_flops(conf: dict, seq: int) -> float:
    """Q.K and P.V, forward and backward: ``12 * heads * head_dim`` per key
    a query sees, per layer."""
    width = conf["num_attention_heads"] * conf["head_dim"]
    return 12.0 * conf["num_hidden_layers"] * width * mean_context(
        seq, conf.get("sliding_window"))


def final_norm_eps(conf: dict) -> float:
    return conf["norm_epsilon"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta):
    """x [S, H, D]: rotate the two halves of each head by position."""
    s, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(conf, mm, kind, p, x):
    hd = conf["head_dim"]
    eps = conf["norm_epsilon"]
    a = layernorm(x, p["ln1/scale"], p["ln1/bias"], eps)
    q = mm("sd,dhk->shk", a, p["attn/wq"]) + p["attn/bq"]
    k = mm("sd,dhk->shk", a, p["attn/wk"]) + p["attn/bk"]
    v = mm("sd,dhk->shk", a, p["attn/wv"]) + p["attn/bv"]
    q = _rope(q * hd ** -0.5, conf["rope_theta"])
    k = _rope(k, conf["rope_theta"])
    o = causal_attention(mm, q, k, v, conf["sliding_window"])
    x = x + mm("shk,hkd->sd", o, p["attn/wo"]) + p["attn/bo"]
    b = layernorm(x, p["ln2/scale"], p["ln2/bias"], eps)
    f = _gelu_tanh(mm("sd,df->sf", b, p["ffn/up"]) + p["ffn/up_b"])
    return x + mm("sf,fd->sd", f, p["ffn/down"]) + p["ffn/down_b"]
