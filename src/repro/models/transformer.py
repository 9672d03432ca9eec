"""Generic model assembly: one ModelConfig drives all 10 assigned
architectures (dense / MoE / MLA / SSM / hybrid / enc-dec / VLM / audio).

Layers are grouped into *periods* (the repeating block pattern, e.g. gemma2's
(sliding, global) pair); parameters of all periods are stacked and traversed
with lax.scan + remat, so compile time is O(1) in depth. Caches mirror the
period structure with a stacked leading dim and ride through the scan as xs/ys.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical_constraint
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import Initializer, stack_layers
from repro.models.layers import (NORMS, dense_mlp, embed, gated_mlp,
                                 init_dense_mlp, init_embedding, init_gated_mlp,
                                 softcap, unembed)

# Block kinds understood by the assembler:
#   attn_full | attn_sw  -- attention + FFN (dense or MoE per cfg.moe)
#   mla | mla_dense      -- deepseek MLA attention + (MoE | first dense) FFN
#   rwkv                 -- RWKV6 time-mix + channel-mix
#   mamba                -- Mamba2 mixer (no FFN)
#   shared_attn          -- zamba2 shared transformer block (+ per-use LoRA)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab: int
    d_model: int
    pattern: tuple[str, ...]            # repeating block kinds (one period)
    num_periods: int                    # total layers = prelude + pattern*periods
    prelude: tuple[str, ...] = ()       # unscanned leading blocks (deepseek)
    # attention
    num_heads: int = 8
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 10000.0
    rope_scaling: tuple | None = None   # MLA YaRN, as (key, value) pairs
    window: int | None = None           # for attn_sw blocks
    attn_softcap: float | None = None
    query_scale: float | None = None
    use_bias: bool = False
    use_rope: bool = True
    # ffn
    d_ff: int = 0
    mlp_kind: str = "gated"             # gated | dense
    act: str = "gelu"
    # norms / embedding
    norm: str = "rms"
    post_norm: bool = False             # gemma2 sandwich norms
    embed_scale: bool = False
    final_softcap: float | None = None
    tie_embeddings: bool = True
    # moe / ssm subconfigs
    moe: moe_lib.MoEConfig | None = None
    first_dense_ff: int = 0
    # MLA dims (deepseek-v2 defaults)
    mla_kv_lora: int = 512
    mla_q_lora: int = 1536
    mla_qk_nope: int = 128
    mla_qk_rope: int = 64
    mla_v: int = 128
    mla_latent_norm: bool = False       # RMSNorm on the MLA latents
    rwkv: ssm_lib.RWKV6Config | None = None
    mamba: ssm_lib.Mamba2Config | None = None
    shared_lora_rank: int = 64          # zamba2 per-use adapters
    # enc-dec (seamless): encoder = non-causal attn_full + dense ffn
    encoder_periods: int = 0
    # modality frontends (stub embeddings consumed as-is)
    prefix_len: int = 0                 # vlm image tokens / audio frames
    modality: str = "text"              # text | vision | audio
    # execution
    remat: str = "full"                 # full | dots | none
    unroll: bool = False                # unroll layer scans (cost-probe mode)
    attn_impl: str = "naive"            # naive | chunked (flash-style)
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.prelude) + len(self.pattern) * self.num_periods

    def attn_cfg(self, kind: str) -> attn.AttnConfig:
        return attn.AttnConfig(
            d_model=self.d_model, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            window=self.window if kind == "attn_sw" else None,
            logit_softcap=self.attn_softcap, query_scale=self.query_scale,
            use_bias=self.use_bias, use_rope=self.use_rope,
            impl=self.attn_impl, q_chunk=self.attn_q_chunk,
            kv_chunk=self.attn_kv_chunk)

    def mla_cfg(self) -> attn.MLAConfig:
        return attn.MLAConfig(d_model=self.d_model, num_heads=self.num_heads,
                              kv_lora=self.mla_kv_lora, q_lora=self.mla_q_lora,
                              qk_nope=self.mla_qk_nope, qk_rope=self.mla_qk_rope,
                              v_dim=self.mla_v, rope_theta=self.rope_theta,
                              latent_norm=self.mla_latent_norm,
                              rope_scaling=self.rope_scaling)


def _norm(cfg, p, x):
    return NORMS[cfg.norm][1](p, x)


def _init_norm(ini, cfg, dim=None):
    return NORMS[cfg.norm][0](ini, dim or cfg.d_model)


def _ffn_kind(cfg: ModelConfig, dense: bool = False) -> str:
    """Static FFN kind: `dense` forces a plain gated MLP (deepseek layer 0,
    zamba2's shared block)."""
    if dense:
        return "gated"
    if cfg.moe is not None:
        return "moe"
    return cfg.mlp_kind


def _init_ffn(ini, cfg: ModelConfig, dense_ff: int | None = None):
    kind = _ffn_kind(cfg, dense_ff is not None)
    if kind == "moe":
        return moe_lib.init_moe(ini, cfg.moe)
    if kind == "gated":
        return init_gated_mlp(ini, cfg.d_model, dense_ff or cfg.d_ff)
    return init_dense_mlp(ini, cfg.d_model, cfg.d_ff)


def _aux(cfg: ModelConfig, loss=None, fill=None) -> dict:
    """A block's auxiliary outputs: its router ``loss`` and, in a model
    with MoE layers, ``moe_layers`` (1 in a MoE block) and the block's
    ``fill`` (``moe.moe_layer``'s slot fill), so that they add up over the
    stack with one structure in every block."""
    zero = jnp.zeros((), jnp.float32)
    out = {"loss": zero if loss is None else loss}
    if cfg.moe is not None:
        out["moe_layers"] = zero if fill is None else jnp.ones((), jnp.float32)
        out["fill"] = zero if fill is None else fill
    return out


def _ffn(fp, cfg: ModelConfig, x, dense: bool = False):
    kind = _ffn_kind(cfg, dense)
    if kind == "moe":
        y, loss, fill = moe_lib.moe_layer(fp, cfg.moe, x)
        return y, _aux(cfg, loss, fill)
    if kind == "gated":
        return gated_mlp(fp, x, cfg.act), _aux(cfg)
    return dense_mlp(fp, x, cfg.act), _aux(cfg)


# ---------------------------------------------------------------------------
# Per-block init / apply / cache
# ---------------------------------------------------------------------------

def init_block(ini: Initializer, cfg: ModelConfig, kind: str):
    p: dict[str, Any] = {"ln1": _init_norm(ini, cfg)}
    if kind in ("attn_full", "attn_sw"):
        p["attn"] = attn.init_attention(ini, cfg.attn_cfg(kind))
        p["ln2"] = _init_norm(ini, cfg)
        p["ffn"] = _init_ffn(ini, cfg)
    elif kind in ("mla", "mla_dense"):
        p["attn"] = attn.init_mla(ini, cfg.mla_cfg())
        p["ln2"] = _init_norm(ini, cfg)
        p["ffn"] = _init_ffn(ini, cfg,
                             cfg.first_dense_ff if kind == "mla_dense" else None)
    elif kind == "rwkv":
        p["tm"] = ssm_lib.init_rwkv6_time_mix(ini, cfg.rwkv)
        p["ln2"] = _init_norm(ini, cfg)
        p["cm"] = ssm_lib.init_rwkv6_channel_mix(ini, cfg.rwkv)
    elif kind == "mamba":
        p["mix"] = ssm_lib.init_mamba2(ini, cfg.mamba)
    elif kind == "shared_attn":
        r = cfg.shared_lora_rank
        p["lora_a"] = ini.normal((2 * cfg.d_model, r), ("embed", None), stddev=0.01)
        p["lora_b"] = ini.normal((r, cfg.d_model), (None, "embed"), stddev=0.01)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if cfg.post_norm and kind in ("attn_full", "attn_sw", "mla", "mla_dense"):
        p["post_ln1"] = _init_norm(ini, cfg)
        p["post_ln2"] = _init_norm(ini, cfg)
    return p


def init_shared_block(ini: Initializer, cfg: ModelConfig):
    """zamba2: one transformer block shared by every shared_attn site; input is
    concat(hidden, initial embedding) projected 2d -> d."""
    return {
        "in_proj": ini.fan_in((2 * cfg.d_model, cfg.d_model), ("embed", "embed")),
        "ln1": _init_norm(ini, cfg),
        "attn": attn.init_attention(ini, cfg.attn_cfg("attn_full")),
        "ln2": _init_norm(ini, cfg),
        "ffn": init_gated_mlp(ini, cfg.d_model, cfg.d_ff),
        "out_proj": ini.fan_in((cfg.d_model, cfg.d_model), ("embed", "embed")),
    }


def _residual(cfg, p, x, delta, post_key):
    if cfg.post_norm and post_key in p:
        delta = _norm(cfg, p[post_key], delta)
    return x + delta


def apply_block(p, cfg: ModelConfig, kind: str, x, *, mode: str,
                cache=None, pos=None, shared=None, emb0=None, causal=True):
    """Apply one block. Returns (x, new_cache, aux) with aux as ``_aux``."""
    no_aux = _aux(cfg)
    if kind in ("attn_full", "attn_sw", "mla", "mla_dense"):
        is_mla = kind.startswith("mla")
        acfg = cfg.mla_cfg() if is_mla else cfg.attn_cfg(kind)
        h = _norm(cfg, p["ln1"], x)
        if is_mla:
            if mode == "train":
                a, nc = attn.mla_train(p["attn"], acfg, h), cache
            elif mode == "prefill":
                a, nc = attn.mla_prefill(p["attn"], acfg, h, cache)
            else:
                a, nc = attn.mla_decode(p["attn"], acfg, h, cache, pos)
        else:
            if mode == "train":
                a, nc = attn.attention_train(p["attn"], acfg, h, causal=causal), cache
            elif mode == "prefill":
                a, nc = attn.attention_prefill(p["attn"], acfg, h, cache)
            else:
                a, nc = attn.attention_decode(p["attn"], acfg, h, cache, pos)
        x = _residual(cfg, p, x, a, "post_ln1")
        f, aux = _ffn(p["ffn"], cfg, _norm(cfg, p["ln2"], x),
                      dense=(kind == "mla_dense"))
        x = _residual(cfg, p, x, f, "post_ln2")
        return x, nc, aux

    if kind == "rwkv":
        h = _norm(cfg, p["ln1"], x)
        st = None if mode == "train" else cache
        if mode == "decode":
            a, tm = ssm_lib.rwkv6_time_mix_step(p["tm"], cfg.rwkv, h, cache)
        else:
            a, tm = ssm_lib.rwkv6_time_mix(p["tm"], cfg.rwkv, h, st)
        x = x + a
        c, cm = ssm_lib.rwkv6_channel_mix(p["cm"], _norm(cfg, p["ln2"], x), st)
        x = x + c
        nc = None if mode == "train" else {**tm, **cm}
        return x, nc, no_aux

    if kind == "mamba":
        h = _norm(cfg, p["ln1"], x)
        a, st = ssm_lib.mamba2_mix(p["mix"], cfg.mamba, h,
                                   None if mode == "train" else cache)
        return x + a, (None if mode == "train" else st), no_aux

    if kind == "shared_attn":
        cat = jnp.concatenate([x, emb0.astype(x.dtype)], axis=-1)
        w_in = shared["in_proj"] + p["lora_a"] @ p["lora_b"]
        h = cat @ w_in
        acfg = cfg.attn_cfg("attn_full")
        h1 = _norm(cfg, shared["ln1"], h)
        if mode == "train":
            a, nc = attn.attention_train(shared["attn"], acfg, h1), cache
        elif mode == "prefill":
            a, nc = attn.attention_prefill(shared["attn"], acfg, h1, cache)
        else:
            a, nc = attn.attention_decode(shared["attn"], acfg, h1, cache, pos)
        h = h + a
        f, _ = _ffn(shared["ffn"], cfg, _norm(cfg, shared["ln2"], h), dense=True)
        h = h + f
        return x + h @ shared["out_proj"], nc, no_aux

    raise ValueError(f"unknown block kind {kind!r}")


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype=None):
    dtype = dtype if dtype is not None else cfg.dtype
    if kind in ("attn_full", "attn_sw", "shared_attn"):
        return attn.init_cache(cfg.attn_cfg(kind if kind != "shared_attn"
                                            else "attn_full"), batch, max_seq,
                               dtype)
    if kind in ("mla", "mla_dense"):
        return attn.init_mla_cache(cfg.mla_cfg(), batch, max_seq, dtype)
    if kind == "rwkv":
        return ssm_lib.init_rwkv6_state(cfg.rwkv, batch, dtype)
    if kind == "mamba":
        return ssm_lib.init_mamba2_state(cfg.mamba, batch, dtype)
    raise ValueError(kind)


def init_model_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Full decode cache: per-block caches; scanned blocks stacked on dim 0.
    Returns (values, axes) trees."""
    vals: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    if cfg.prelude:
        vals["prelude"], axes["prelude"] = {}, {}
        for j, kind in enumerate(cfg.prelude):
            v, a = init_block_cache(cfg, kind, batch, max_seq)
            vals["prelude"][f"p{j}_{kind}"] = v
            axes["prelude"][f"p{j}_{kind}"] = a
    bvals, baxes = {}, {}
    for j, kind in enumerate(cfg.pattern):
        v, a = init_block_cache(cfg, kind, batch, max_seq)
        bvals[f"b{j}_{kind}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.num_periods,) + x.shape), v)
        baxes[f"b{j}_{kind}"] = jax.tree.map(
            lambda ax: ("layers",) + ax, a,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t))
    vals["blocks"], axes["blocks"] = bvals, baxes
    if cfg.encoder_periods:
        acfg = cfg.attn_cfg("attn_full")
        kv = (batch, cfg.prefix_len, acfg.num_kv_heads, acfg.head_dim)
        cvals = {"k": jnp.zeros((cfg.num_periods,) + kv, cfg.dtype),
                 "v": jnp.zeros((cfg.num_periods,) + kv, cfg.dtype)}
        caxes_leaf = ("layers", "batch", "seq", "kv_heads", "head_dim")
        vals["cross"] = cvals
        axes["cross"] = {"k": caxes_leaf, "v": caxes_leaf}
    return vals, axes


def model_cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    """(ShapeDtypeStruct tree, axes tree) for the decode cache — no device
    allocation (dry-run safe). Axes are size-independent, so they come from a
    minimal concrete init."""
    vals_sds = jax.eval_shape(lambda: init_model_cache(cfg, batch, max_seq)[0])
    _, axes = init_model_cache(cfg, 1, 8)
    return vals_sds, axes


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_model(key: jax.Array, cfg: ModelConfig):
    k_embed, k_pre, k_stack, k_shared, k_enc, k_fin = jax.random.split(key, 6)
    ini = Initializer(k_embed, cfg.dtype)
    params: dict[str, Any] = {"embed": init_embedding(ini, cfg.vocab, cfg.d_model)}

    def period_init(i: Initializer):
        return {f"b{j}_{kind}": init_block(i, cfg, kind)
                for j, kind in enumerate(cfg.pattern)}

    if cfg.prelude:
        pre_ini = Initializer(k_pre, cfg.dtype)
        params["prelude"] = {f"p{j}_{kind}": init_block(pre_ini, cfg, kind)
                             for j, kind in enumerate(cfg.prelude)}
    params["blocks"] = stack_layers(period_init, k_stack, cfg.num_periods, cfg.dtype)
    if "shared_attn" in cfg.pattern:
        params["shared"] = init_shared_block(Initializer(k_shared, cfg.dtype), cfg)
    if cfg.encoder_periods:
        enc_cfg = dataclasses.replace(cfg, moe=None, mlp_kind=cfg.mlp_kind)
        def enc_period(i: Initializer):
            return {"blk": init_block(i, enc_cfg, "attn_full")}
        params["encoder"] = stack_layers(enc_period, k_enc, cfg.encoder_periods,
                                         cfg.dtype)
        params["enc_final_ln"] = _init_norm(Initializer(k_fin, cfg.dtype), cfg)
        def cross_init(i: Initializer):
            return {f"x{j}": {"ln": _init_norm(i, cfg),
                              "attn": attn.init_attention(i, cfg.attn_cfg("attn_full"))}
                    for j, k_ in enumerate(cfg.pattern)}
        params["cross"] = stack_layers(cross_init, k_fin, cfg.num_periods, cfg.dtype)
    params["final_ln"] = _init_norm(Initializer(k_fin, cfg.dtype), cfg)
    return params


# ---------------------------------------------------------------------------
# Stack traversal
# ---------------------------------------------------------------------------

def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


def _cross_apply(xp, cfg, x, mode, enc_out=None, cross_cache=None):
    """Cross-attention sublayer for enc-dec decoders."""
    h = _norm(cfg, xp["ln"], x)
    acfg = cfg.attn_cfg("attn_full")
    if mode in ("train", "prefill"):
        y = attn.attention_train(xp["attn"], acfg, h, kv_x=enc_out, causal=False)
        nc = (attn.init_cross_cache(acfg, xp["attn"], enc_out, cfg.dtype)
              if mode == "prefill" else None)
    else:
        y = attn.cross_attention_step(xp["attn"], acfg, h, cross_cache)
        nc = cross_cache
    return x + y, nc


def _add(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in a}


def run_stack(params, cfg: ModelConfig, x, *, mode, caches=None, pos=None,
              emb0=None, enc_out=None, causal=True):
    """Run prelude + scanned periods. Returns (x, new_caches, aux), aux
    summed over the blocks (``_aux``)."""
    aux0 = _aux(cfg)
    new_caches: dict[str, Any] = {}
    shared = params.get("shared")
    has_cache = caches is not None
    has_cross = "cross" in params

    if cfg.prelude:
        new_caches["prelude"] = {}
        for j, kind in enumerate(cfg.prelude):
            name = f"p{j}_{kind}"
            c = caches["prelude"][name] if has_cache else None
            x, nc, aux = apply_block(params["prelude"][name], cfg, kind, x,
                                     mode=mode, cache=c, pos=pos, shared=shared,
                                     emb0=emb0, causal=causal)
            aux0 = _add(aux0, aux)
            new_caches["prelude"][name] = nc

    def period_fn(carry, scanned):
        x, aux_acc = carry
        bp = scanned["params"]
        bc = scanned.get("caches")
        xp = scanned.get("cross")
        xc = scanned.get("cross_cache")
        out_caches: dict[str, Any] = {}
        for j, kind in enumerate(cfg.pattern):
            name = f"b{j}_{kind}"
            c = bc[name] if bc is not None else None
            x, nc, aux = apply_block(bp[name], cfg, kind, x, mode=mode, cache=c,
                                     pos=pos, shared=shared, emb0=emb0,
                                     causal=causal)
            aux_acc = _add(aux_acc, aux)
            if nc is not None:
                out_caches[name] = nc
            if xp is not None:
                x, xnc = _cross_apply(xp[f"x{j}"], cfg, x, mode,
                                      enc_out=enc_out, cross_cache=xc)
                if xnc is not None:
                    out_caches["__cross__"] = xnc
        return (x, aux_acc), out_caches

    scanned: dict[str, Any] = {"params": params["blocks"]}
    if has_cache:
        scanned["caches"] = caches["blocks"]
    if has_cross:
        scanned["cross"] = params["cross"]
        if has_cache and mode == "decode":
            scanned["cross_cache"] = caches["cross"]

    fn = _remat(cfg, period_fn) if mode == "train" else period_fn
    (x, aux0), ys = jax.lax.scan(fn, (x, aux0), scanned, unroll=cfg.unroll)
    if has_cache or mode == "prefill":
        blocks_out = {k: v for k, v in ys.items() if k != "__cross__"}
        new_caches["blocks"] = blocks_out
        if "__cross__" in ys:
            new_caches["cross"] = ys["__cross__"]
        elif has_cross and has_cache:
            new_caches["cross"] = caches["cross"]
    return x, (new_caches if new_caches else None), aux0


def encode(params, cfg: ModelConfig, enc_embeds):
    """Non-causal encoder over stub frontend embeddings [B, F, d]."""
    enc_cfg = dataclasses.replace(cfg, moe=None)

    def period_fn(x, bp):
        x, _, _ = apply_block(bp["blk"], enc_cfg, "attn_full", x,
                              mode="train", causal=False)
        return x, None

    fn = _remat(cfg, period_fn)
    x, _ = jax.lax.scan(fn, enc_embeds, params["encoder"], unroll=cfg.unroll)
    return _norm(cfg, params["enc_final_ln"], x)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch, mode):
    x = embed(params["embed"], batch["tokens"], cfg.embed_scale)
    x = x.astype(cfg.dtype)
    x = logical_constraint(x, ("batch", "seq", "embed"))
    if cfg.prefix_len and "prefix" in batch and mode != "decode":
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
    return x


def forward_train(params, cfg: ModelConfig, batch):
    """batch: tokens [B,S] (+ prefix [B,P,d] | enc_embeds [B,F,d]).
    Returns (logits [B,S,V], aux_loss)."""
    logits, aux, _ = forward_train_counted(params, cfg, batch)
    return logits, aux


def forward_train_counted(params, cfg: ModelConfig, batch):
    """``forward_train`` with the model's counters: ``(logits, aux_loss,
    counters)``, where a model with MoE layers counts ``expert_slot_fill``,
    the mean over its MoE layers of the share (%) of the held experts' slot
    rows that hold a routed choice."""
    x = _embed_inputs(params, cfg, batch, "train")
    emb0 = x
    enc_out = (encode(params, cfg, batch["enc_embeds"].astype(cfg.dtype))
               if cfg.encoder_periods else None)
    x, _, aux = run_stack(params, cfg, x, mode="train", emb0=emb0,
                          enc_out=enc_out)
    x = _norm(cfg, params["final_ln"], x)
    if cfg.prefix_len and "prefix" in batch:
        x = x[:, batch["prefix"].shape[1]:]
    logits = softcap(unembed(params["embed"], x), cfg.final_softcap)
    counters = {}
    if cfg.moe is not None:
        counters["expert_slot_fill"] = 100.0 * aux["fill"] / jnp.maximum(
            aux["moe_layers"], 1.0)
    return (logical_constraint(logits, ("batch", "seq", "vocab")),
            aux["loss"], counters)


def forward_prefill(params, cfg: ModelConfig, batch, caches):
    """Prompt pass filling caches; returns (last-position logits, caches)."""
    x = _embed_inputs(params, cfg, batch, "prefill")
    emb0 = x
    enc_out = (encode(params, cfg, batch["enc_embeds"].astype(cfg.dtype))
               if cfg.encoder_periods else None)
    x, new_caches, _ = run_stack(params, cfg, x, mode="prefill", caches=caches,
                                 emb0=emb0, enc_out=enc_out)
    x = _norm(cfg, params["final_ln"], x[:, -1:])
    logits = softcap(unembed(params["embed"], x), cfg.final_softcap)
    return logical_constraint(logits, ("batch", "seq", "vocab")), new_caches


def forward_decode(params, cfg: ModelConfig, tokens, caches, pos):
    """One-token decode. tokens [B,1]; pos scalar int32."""
    x = embed(params["embed"], tokens, cfg.embed_scale).astype(cfg.dtype)
    emb0 = x
    x, new_caches, _ = run_stack(params, cfg, x, mode="decode", caches=caches,
                                 pos=pos, emb0=emb0)
    x = _norm(cfg, params["final_ln"], x)
    logits = softcap(unembed(params["embed"], x), cfg.final_softcap)
    return logical_constraint(logits, ("batch", "seq", "vocab")), new_caches
