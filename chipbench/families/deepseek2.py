"""DeepSeek-V2 (arXiv:2405.04434), written from section 2.1 (multi-head
latent attention, MLA: low-rank joint compression of keys and values,
2.1.2, and the decoupled rotary key, 2.1.3) and section 2.2 (DeepSeekMoE:
shared and fine-grained routed experts, 2.2.1; the expert-level balance
loss, 2.2.3; dropping at capacity, 2.2.4), with YaRN (arXiv:2309.00071)
for the long-context extension of section 3.1, in float32.

Layers: ``first_k_dense_replace`` leading blocks of kind ``mla_dense``
(MLA, then a SwiGLU FFN of width ``intermediate_size``) under
``prelude/p<j>_mla_dense/``, then the rest stacked under
``blocks/b0_mla/`` (MLA, then the MoE FFN); the final norm is an RMSNorm
with a scale only. Every option is read from the configuration file:

- ``q_lora_rank``: queries through a rank-``q_lora_rank`` latent
  (``attn/q_down``, ``attn/q_up``), or straight from ``attn/wq`` where it
  is null;
- ``latent_norm``: an RMSNorm on each latent (``attn/q_norm/scale`` where
  queries have one, ``attn/kv_norm/scale``), as published, or none;
- ``rope_scaling``: YaRN (``type`` or ``rope_type`` "yarn": frequencies
  blended between the plain and the interpolated ones over the ramp of
  ``beta_fast`` / ``beta_slow`` rotations, cos and sin scaled by
  ``mscale(mscale) / mscale(mscale_all_dim)``, the softmax scale by
  ``mscale(mscale_all_dim)**2``), or plain RoPE where it is null;
- the router: softmax over all ``n_routed_experts``, greedy top
  ``num_experts_per_tok`` (``topk_method`` "greedy", ``scoring_func``
  "softmax": others are refused), weights renormalised where
  ``norm_topk_prob`` holds and top-k is over 1, else times
  ``routed_scaling_factor``, as the published ``MoEGate`` does;
- held experts: this chip holds ``experts_held`` routed experts from
  ``expert_offset`` (``ffn/w_gate``, ``ffn/w_up``, ``ffn/w_down``) and
  computes only their part of the result, as one chip of an expert-
  parallel layer does; the ``n_shared_experts`` shared experts
  (``ffn/shared/*``, one SwiGLU of width ``n_shared_experts *
  moe_intermediate_size``) are computed whole;
- ``capacity_factor``: per row, a choice is dropped once its expert holds
  ``capacity`` choices in (token, choice) order, ``capacity`` as the
  program's ``MoEConfig.capacity`` gives it from the row's length and the
  published expert count; null drops nothing;
- the loss terms: ``aux_loss_alpha * E * sum_i f_i P_i`` over all ``E``
  routed experts (``f_i`` the share of all top-k choices that went to
  expert i, dropped ones too; ``P_i`` its mean router probability),
  combined over the worker's local batch, or per row and then averaged
  over rows with ``seq_aux``; plus ``router_z_loss_coef`` times the mean
  squared log-sum-exp of the router logits where that is not 0.

Departures from the published model, none of which changes what a layer
computes from its weights:

- an RMSNorm multiplies by ``1 + scale``, the program's convention for
  its weights (made at zero); published, by ``scale`` (made at one);
- RoPE rotates the two halves of each rotary head (``[x1, x2]``); the
  published code rotates interleaved pairs: a fixed permutation of the
  rotary columns of the query and key weights;
- the shared rotary key has a leaf of its own, ``attn/k_rope``; published,
  it is the last ``qk_rope_head_dim`` columns of ``kv_a_proj_with_mqa``;
- the unembedding is tied to the embedding (the harness ties it; the
  published model does not);
- left out: device-limited routing (2.2.2) and group-limited top-k; the
  device-level and communication balance losses (2.2.3); the paper's
  device-level dropping by affinity, with some sequences never dropped
  (2.2.4), in favour of the program's per-row rule; the z-loss is the
  program's, not the paper's;
- each held expert computes every token of the row and its output is
  weighted by the token's gate, which is 0 where the token did not choose
  it or the choice was dropped: the same sum as a sparse dispatch.
"""
from __future__ import annotations

import fractions
import math

import jax
import jax.numpy as jnp

from chipbench.reference import causal_attention

MATMUL = ("attn/q_down", "attn/q_up", "attn/wq", "attn/kv_down",
          "attn/k_rope", "attn/k_up", "attn/v_up", "attn/wo",
          "ffn/gate", "ffn/up", "ffn/down", "ffn/router",
          "ffn/w_gate", "ffn/w_up", "ffn/w_down",
          "ffn/shared/gate", "ffn/shared/up", "ffn/shared/down")
ROUTED = ("ffn/w_gate", "ffn/w_up", "ffn/w_down")
PERIOD = ("mla",)


def _check(conf: dict) -> None:
    for key, want in (("moe_layer_freq", 1), ("scoring_func", "softmax"),
                      ("topk_method", "greedy"), ("hidden_act", "silu")):
        if conf[key] != want:
            raise ValueError(f"deepseek2: {key} {conf[key]!r} is not "
                             f"modelled here (only {want!r})")
    if not (0 <= conf["expert_offset"] and conf["expert_offset"]
            + conf["experts_held"] <= conf["n_routed_experts"]):
        raise ValueError("deepseek2: held experts outside the routed ones")


def prelude(conf: dict) -> tuple:
    return ("mla_dense",) * conf["first_k_dense_replace"]


def block(conf: dict, kind: str) -> dict:
    _check(conf)
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    vd, kv, ql = conf["v_head_dim"], conf["kv_lora_rank"], conf["q_lora_rank"]
    out = {"ln1/scale": (d,), "ln2/scale": (d,),
           "attn/kv_down": (d, kv), "attn/k_rope": (d, rope),
           "attn/k_up": (kv, h, nope), "attn/v_up": (kv, h, vd),
           "attn/wo": (h, vd, d)}
    if ql:
        out.update({"attn/q_down": (d, ql), "attn/q_up": (ql, h, nope + rope)})
    else:
        out["attn/wq"] = (d, h, nope + rope)
    if conf["latent_norm"]:
        out["attn/kv_norm/scale"] = (kv,)
        if ql:
            out["attn/q_norm/scale"] = (ql,)
    if kind == "mla_dense":
        f = conf["intermediate_size"]
        out.update({"ffn/gate": (d, f), "ffn/up": (d, f), "ffn/down": (f, d)})
    elif kind == "mla":
        e, held = conf["n_routed_experts"], conf["experts_held"]
        fe = conf["moe_intermediate_size"]
        out.update({"ffn/router": (d, e), "ffn/w_gate": (held, d, fe),
                    "ffn/w_up": (held, d, fe), "ffn/w_down": (held, fe, d)})
        if conf["n_shared_experts"]:
            fs = conf["n_shared_experts"] * fe
            out.update({"ffn/shared/gate": (d, fs), "ffn/shared/up": (d, fs),
                        "ffn/shared/down": (fs, d)})
    else:
        raise ValueError(f"deepseek2: no block kind {kind!r}")
    return out


def final_norm(conf: dict) -> dict:
    return {"scale": (conf["hidden_size"],)}


def final_norm_apply(conf: dict, p: dict, x):
    return rmsnorm(conf, x, p["scale"])


def _plain(value):
    """A config group as a plain dict (or None): the program may hold one
    as a tuple of pairs to keep its config hashable."""
    if value is None or isinstance(value, dict):
        return value
    return dict(value)


def program_sizes(cfg) -> dict:
    """The file's keys as the program's ``ModelConfig`` holds them. A key
    the program has no field for yet reads the value that the program's
    code implies, and the field named here once the program adds it:
    ``rope_scaling``, ``mla_latent_norm``, ``norm_eps``;
    ``moe.experts_held``, ``moe.expert_offset``,
    ``moe.routed_scaling_factor``, ``moe.scoring_func``,
    ``moe.topk_method``, ``moe.seq_aux``."""
    moe = cfg.moe
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads,      # MLA: a key per head
        "q_lora_rank": cfg.mla_q_lora or None,
        "kv_lora_rank": cfg.mla_kv_lora, "qk_nope_head_dim": cfg.mla_qk_nope,
        "qk_rope_head_dim": cfg.mla_qk_rope, "v_head_dim": cfg.mla_v,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": _plain(getattr(cfg, "rope_scaling", None)),
        "latent_norm": getattr(cfg, "mla_latent_norm", False),
        "rms_norm_eps": getattr(cfg, "norm_eps", 1e-6),
        "attention_bias": False,
        "first_k_dense_replace": (len(cfg.prelude) if set(cfg.prelude)
                                  <= {"mla_dense"} else cfg.prelude),
        "moe_layer_freq": 1 if cfg.pattern == ("mla",) else cfg.pattern,
        "num_hidden_layers": cfg.num_layers,
        "intermediate_size": cfg.first_dense_ff,
        "hidden_act": cfg.act if cfg.act == moe.act else (cfg.act, moe.act),
        "moe_intermediate_size": moe.d_expert,
        "n_routed_experts": moe.num_experts,
        "experts_held": getattr(moe, "experts_held", None) or moe.num_experts,
        "expert_offset": getattr(moe, "expert_offset", 0),
        "num_experts_per_tok": moe.top_k,
        "n_shared_experts": moe.num_shared,
        "norm_topk_prob": moe.normalize_weights,
        "routed_scaling_factor": getattr(moe, "routed_scaling_factor", 1.0),
        "scoring_func": getattr(moe, "scoring_func", "softmax"),
        "topk_method": getattr(moe, "topk_method", "greedy"),
        "capacity_factor": moe.capacity_factor,
        "seq_aux": getattr(moe, "seq_aux", False),
        "aux_loss_alpha": moe.aux_loss_coef,
        "router_z_loss_coef": moe.z_loss_coef,
        "vocab_size": cfg.vocab,
        "tie_word_embeddings": True,     # the program always ties
    }


def routed_share(conf: dict):
    """The share of a routed expert's weight one token uses on average."""
    return fractions.Fraction(conf["num_experts_per_tok"],
                              conf["n_routed_experts"])


def mixing_flops(conf: dict, seq: int) -> float:
    """Q.K over ``qk_nope + qk_rope`` and P.V over ``v_head_dim``, forward
    and backward, per causal key a query sees (``(seq + 1) / 2`` on
    average), in every layer."""
    width = conf["num_attention_heads"] * (
        conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        + conf["v_head_dim"])
    return 6.0 * conf["num_hidden_layers"] * width * (seq + 1) / 2


# ---------------------------------------------------------------------------
# layer
# ---------------------------------------------------------------------------

def rmsnorm(conf, x, scale):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + conf["rms_norm_eps"]) * (1.0 + scale)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_frequencies(conf: dict):
    """``(inverse frequencies [qk_rope / 2], cos and sin scale, softmax
    scale factor)`` of the rotary head, plain or YaRN."""
    dim, base = conf["qk_rope_head_dim"], conf["rope_theta"]
    half = dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    y = conf["rope_scaling"]
    if y is None:
        return inv, 1.0, 1.0
    if y.get("type", y.get("rope_type")) != "yarn":
        raise ValueError(f"deepseek2: rope_scaling {y!r} is not YaRN")
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp               # where the plain frequency is kept
    inv = inv / factor * (1.0 - extra) + inv * extra
    m, m_all = y.get("mscale", 1), y.get("mscale_all_dim", 0)
    cos_sin = yarn_mscale(factor, m) / yarn_mscale(factor, m_all)
    softmax = yarn_mscale(factor, m_all) ** 2 if m_all else 1.0
    return inv, cos_sin, softmax


def _rope(x, inv, scale):
    """x [S, H, D]: rotate the two halves of each head by position."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    sin = (jnp.sin(ang) * scale)[:, None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(conf, mm, p, x):
    """Multi-head latent attention of one row x [S, d] (2.1.2-2.1.3)."""
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    h = conf["num_attention_heads"]
    if conf["q_lora_rank"]:
        cq = mm("sd,dl->sl", x, p["attn/q_down"])
        if conf["latent_norm"]:
            cq = rmsnorm(conf, cq, p["attn/q_norm/scale"])
        q = mm("sl,lhk->shk", cq, p["attn/q_up"])
    else:
        q = mm("sd,dhk->shk", x, p["attn/wq"])
    ckv = mm("sd,dl->sl", x, p["attn/kv_down"])
    if conf["latent_norm"]:
        ckv = rmsnorm(conf, ckv, p["attn/kv_norm/scale"])
    inv, cos_sin, softmax = rope_frequencies(conf)
    k_rope = _rope(mm("sd,dr->sr", x, p["attn/k_rope"])[:, None, :], inv,
                   cos_sin)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cos_sin)],
                        -1) * ((nope + rope) ** -0.5 * softmax)
    k = jnp.concatenate(
        [mm("sl,lhk->shk", ckv, p["attn/k_up"]),
         jnp.broadcast_to(k_rope, (x.shape[0], h, rope))], -1)
    v = mm("sl,lhk->shk", ckv, p["attn/v_up"])
    return mm("shk,hkd->sd", causal_attention(mm, q, k, v), p["attn/wo"])


def swiglu(mm, x, gate, up, down):
    h = jax.nn.silu(mm("sd,df->sf", x, gate)) * mm("sd,df->sf", x, up)
    return mm("sf,fd->sd", h, down)


def capacity(conf: dict, tokens: int) -> int:
    """Choices an expert takes from a row of ``tokens``, as the program's
    ``MoEConfig.capacity`` gives it."""
    c = int(tokens * conf["num_experts_per_tok"] * conf["capacity_factor"]
            / conf["n_routed_experts"]) + 1
    return max(4, -(-c // 4) * 4)


def route(conf, mm, p, x):
    """The router on one row x [S, d]: ``(gate [S, E], stats)``, the gate
    being each token's weight on each routed expert after top-k,
    renormalisation, scaling and dropping at capacity."""
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    s = x.shape[0]
    logits = mm("sd,de->se", x, p["ffn/router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    if k > 1 and conf["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * conf["routed_scaling_factor"]
    chosen = jax.nn.one_hot(ids, e, dtype=jnp.float32)       # [S, k, E]
    kept = jnp.ones((s, k), jnp.float32)
    if conf["capacity_factor"] is not None:
        flat = chosen.reshape(s * k, e)
        before = jnp.sum((jnp.cumsum(flat, 0) - flat) * flat, -1)
        kept = (before < capacity(conf, s)).astype(jnp.float32).reshape(s, k)
    gate = jnp.einsum("ske,sk->se", chosen, w * kept,
                      precision=jax.lax.Precision.HIGHEST)
    stats = {"probs": jnp.sum(probs, 0), "counts": jnp.sum(chosen, (0, 1)),
             "tokens": jnp.float32(s),
             "z": jnp.sum(jnp.square(jax.nn.logsumexp(logits, -1))),
             "dropped": s * k - jnp.sum(kept)}
    return gate, stats


def moe(conf, mm, p, x):
    """The DeepSeekMoE FFN of one row (2.2.1): this chip's routed experts'
    part of the result plus the shared experts; and the router's stats."""
    gate, stats = route(conf, mm, p, x)
    off, held = conf["expert_offset"], conf["experts_held"]
    g = gate[:, off:off + held]                                # [S, held]
    h = jax.nn.silu(mm("sd,edf->esf", x, p["ffn/w_gate"])) \
        * mm("sd,edf->esf", x, p["ffn/w_up"])
    y = jnp.sum(g.T[:, :, None] * mm("esf,efd->esd", h, p["ffn/w_down"]), 0)
    if conf["n_shared_experts"]:
        y = y + swiglu(mm, x, p["ffn/shared/gate"], p["ffn/shared/up"],
                       p["ffn/shared/down"])
    return y, stats


def layer(conf, mm, kind, p, x):
    x = x + mla(conf, mm, p, rmsnorm(conf, x, p["ln1/scale"]))
    b = rmsnorm(conf, x, p["ln2/scale"])
    if kind == "mla_dense":
        return x + swiglu(mm, b, p["ffn/gate"], p["ffn/up"], p["ffn/down"])
    y, stats = moe(conf, mm, p, b)
    return x + y, stats


def router_loss(conf: dict, stats: list):
    """The balance term (and the z-loss) of every MoE layer, from each
    row's statistics [B, ...]: combined over the batch, or per row and
    averaged over rows with ``seq_aux``."""
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    total = jnp.float32(0.0)
    for st in stats:
        n = st["tokens"]
        if conf["seq_aux"]:
            me = st["probs"] / n[:, None]
            ce = st["counts"] / (n[:, None] * k)
            balance = jnp.mean(jnp.sum(me * ce, -1))
        else:
            me = jnp.sum(st["probs"], 0) / jnp.sum(n)
            ce = jnp.sum(st["counts"], 0) / (jnp.sum(n) * k)
            balance = jnp.sum(me * ce)
        total = total + conf["aux_loss_alpha"] * e * balance
        if conf["router_z_loss_coef"]:
            total = total + conf["router_z_loss_coef"] * (
                jnp.sum(st["z"]) / jnp.sum(n))
    return total
