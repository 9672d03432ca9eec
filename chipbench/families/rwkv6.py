"""RWKV-6 "Finch" (arXiv:2404.05892): token shift with data-dependent
LoRA mixing, the exact per-token WKV recurrence with per-channel decay and
bonus, per-head group norm, squared-ReLU channel mix."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import layernorm

MATMUL = ("tm/wr", "tm/wk", "tm/wv", "tm/wg", "tm/wo", "tm/lora_a",
          "tm/lora_b", "tm/w_lora_a", "tm/w_lora_b", "cm/wk", "cm/wv",
          "cm/wr")
PERIOD = ("rwkv",)


def block(conf: dict, kind: str) -> dict:
    d, hd, f = (conf["hidden_size"], conf["head_size"],
                conf["intermediate_size"])
    r1, r2 = conf["time_mix_extra_dim"], conf["time_decay_extra_dim"]
    return {"tm/mu_x": (d,), "tm/mu": (5, d), "tm/lora_a": (d, 5 * r1),
            "tm/lora_b": (5, r1, d), "tm/w0": (d,),
            "tm/w_lora_a": (d, r2), "tm/w_lora_b": (r2, d),
            "tm/wr": (d, d), "tm/wk": (d, d), "tm/wv": (d, d),
            "tm/wg": (d, d), "tm/wo": (d, d), "tm/u": (d // hd, hd),
            "tm/ln_scale": (d,), "tm/ln_bias": (d,),
            "cm/mu_k": (d,), "cm/mu_r": (d,), "cm/wk": (d, f),
            "cm/wv": (f, d), "cm/wr": (d, d),
            "ln1/scale": (d,), "ln1/bias": (d,), "ln2/scale": (d,),
            "ln2/bias": (d,)}


def program_sizes(cfg) -> dict:
    r = cfg.rwkv
    return {"hidden_size": cfg.d_model, "head_size": r.head_dim,
            "intermediate_size": r.d_ff, "time_mix_extra_dim": r.tm_lora,
            "time_decay_extra_dim": r.w_lora, "vocab_size": cfg.vocab,
            "num_hidden_layers": cfg.num_layers,
            "tie_word_embeddings": True}


def mixing_flops(conf: dict, seq: int) -> float:
    """The WKV recurrence: a head_size^2 state update and readout per head,
    forward and backward, ``12 * hidden * head_size`` per layer."""
    return 12.0 * conf["num_hidden_layers"] * conf["hidden_size"] \
        * conf["head_size"]


def final_norm_eps(conf: dict) -> float:
    return conf["layer_norm_epsilon"]


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _wkv(r, k, v, w, u, chunk=64):
    """The RWKV-6 recurrence, token by token: out_t = r_t (S + u k_t v_t^T),
    then S <- diag(w_t) S + k_t v_t^T. [S, H, K] inputs, w the decay in
    (0, 1). Checkpointed per chunk of tokens so the backward pass keeps one
    state per chunk."""
    s, h, dk = r.shape

    def tok(S, xs):
        r_t, k_t, v_t, w_t = xs
        kv = k_t[:, :, None] * v_t[:, None, :]
        out = jnp.sum(r_t[:, :, None] * (S + u[:, :, None] * kv), axis=1)
        return w_t[:, :, None] * S + kv, out

    @jax.checkpoint
    def blk(S, xs):
        return jax.lax.scan(tok, S, xs)

    c = min(chunk, s)
    xs = tuple(a.reshape(s // c, c, h, dk) for a in (r, k, v, w))
    _, out = jax.lax.scan(blk, jnp.zeros((h, dk, dk), jnp.float32), xs)
    return out.reshape(s, h, dk)


def layer(conf, mm, kind, p, x):
    d, hd = conf["hidden_size"], conf["head_size"]
    nh, eps = d // hd, conf["layer_norm_epsilon"]
    s = x.shape[0]
    a = layernorm(x, p["ln1/scale"], p["ln1/bias"], eps)
    dx = _shift(a) - a
    xxx = a + dx * p["tm/mu_x"]
    lora = jnp.tanh(mm("sd,dr->sr", xxx, p["tm/lora_a"]))
    lora = lora.reshape(s, 5, -1)
    dyn = mm("sfr,frd->sfd", lora, p["tm/lora_b"])
    xr, xk, xv, xw, xg = [a + dx * (p["tm/mu"][i] + dyn[:, i])
                          for i in range(5)]
    r = mm("sd,de->se", xr, p["tm/wr"]).reshape(s, nh, hd)
    k = mm("sd,de->se", xk, p["tm/wk"]).reshape(s, nh, hd)
    v = mm("sd,de->se", xv, p["tm/wv"]).reshape(s, nh, hd)
    g = jax.nn.silu(mm("sd,de->se", xg, p["tm/wg"]))
    logw = -jnp.exp(p["tm/w0"] + mm(
        "sr,rd->sd", jnp.tanh(mm("sd,dr->sr", xw, p["tm/w_lora_a"])),
        p["tm/w_lora_b"]))
    o = _wkv(r, k, v, jnp.exp(logw).reshape(s, nh, hd), p["tm/u"])
    mean = jnp.mean(o, -1, keepdims=True)
    var = jnp.mean(jnp.square(o - mean), -1, keepdims=True)
    o = ((o - mean) / jnp.sqrt(var + conf["group_norm_epsilon"])).reshape(s, d)
    o = o * p["tm/ln_scale"] + p["tm/ln_bias"]
    x = x + mm("sd,de->se", o * g, p["tm/wo"])
    b = layernorm(x, p["ln2/scale"], p["ln2/bias"], eps)
    db = _shift(b) - b
    kk = jnp.square(jax.nn.relu(mm("sd,df->sf", b + db * p["cm/mu_k"],
                                   p["cm/wk"])))
    rr = jax.nn.sigmoid(mm("sd,de->se", b + db * p["cm/mu_r"], p["cm/wr"]))
    return x + rr * mm("sf,fd->sd", kk, p["cm/wv"])
