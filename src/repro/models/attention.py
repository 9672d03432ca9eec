"""Attention variants: GQA/MQA with RoPE, sliding windows, logit softcap,
cross-attention, and DeepSeek-V2 MLA (latent KV compression; queries with
or without their own latent, optional RMSNorms on the latents, plain RoPE
or YaRN) with the absorbed-projection decode path. All functions are pure; caches are dicts of
arrays handled functionally.

Cache layouts (per layer):
  full    : k,v [B, S_max, Hkv, D]; decode writes at scalar `pos`.
  window  : k,v [B, W, Hkv, D] ring buffer (slot = pos % W).
  mla     : c_kv [B, S_max, kv_lora], k_rope [B, S_max, qk_rope].
  cross   : k,v [B, S_enc, Hkv, D] computed once from the encoder output.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import logical_constraint
from repro.models.common import Initializer
from repro.models.layers import (apply_rope, rmsnorm, rope_table, softcap,
                                 yarn_softmax_scale)

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: int | None = None          # sliding-window size (None = global)
    logit_softcap: float | None = None
    query_scale: float | None = None   # default head_dim ** -0.5
    use_bias: bool = False
    use_rope: bool = True
    impl: str = "naive"                # naive | chunked (flash-style)
    q_chunk: int = 512
    kv_chunk: int = 1024

    @property
    def scale(self) -> float:
        return self.query_scale if self.query_scale is not None else self.head_dim ** -0.5


def init_attention(ini: Initializer, cfg: AttnConfig):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {"wq": ini.fan_in((d, h, hd), ("embed", "heads", "head_dim")),
         "wk": ini.fan_in((d, kv, hd), ("embed", "kv_heads", "head_dim")),
         "wv": ini.fan_in((d, kv, hd), ("embed", "kv_heads", "head_dim")),
         "wo": ini.fan_in((h, hd, d), ("heads", "head_dim", "embed"), in_dim_idx=1)}
    if cfg.use_bias:
        p["bq"] = ini.zeros((h, hd), ("heads", "head_dim"))
        p["bk"] = ini.zeros((kv, hd), ("kv_heads", "head_dim"))
        p["bv"] = ini.zeros((kv, hd), ("kv_heads", "head_dim"))
        p["bo"] = ini.zeros((d,), ("embed",))
    return p


def _qkv(p, cfg: AttnConfig, x, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q * cfg.scale, k, v


def _sdpa(cfg: AttnConfig, q, k, v, mask):
    """q [B,Sq,H,D], k/v [B,Sk,Hkv,D], mask [B|1, Sq, Sk] bool."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    q = q.reshape(b, sq, kvh, groups, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = softcap(scores, cfg.logit_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(b, sq, h, d)
    out = logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
    return out


def _proj_out(p, cfg: AttnConfig, out):
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.use_bias:
        y = y + p["bo"]
    return y


def _sdpa_chunked(cfg: AttnConfig, q, k, v, *, causal: bool,
                  q_offset: int | jax.Array = 0):
    """Flash-style attention: double scan over (query-chunk x kv-chunk) with
    online softmax — O(Qc*Kc) score materialization instead of O(Sq*Sk).
    This is the memory hillclimb for train_4k/prefill_32k (see EXPERIMENTS.md
    section Perf). q [B,Sq,H,D]; k,v [B,Sk,Hkv,D]. q_offset: global position
    of q[0] (prefill windows)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    qc = min(cfg.q_chunk, sq)
    kc = min(cfg.kv_chunk, sk)
    if sq % qc or sk % kc:                       # fallback for ragged shapes
        mask = (causal_mask(sq, sk, cfg.window) if causal
                else jnp.ones((1, sq, sk), bool))
        return _sdpa(cfg, q, k, v, mask)
    nq, nk = sq // qc, sk // kc

    qr = q.reshape(b, nq, qc, kvh, groups, d).transpose(1, 0, 3, 4, 2, 5)
    kr = k.reshape(b, nk, kc, kvh, d).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(b, nk, kc, kvh, d).transpose(1, 0, 3, 2, 4)
    # qr [nq,B,KV,G,qc,D]; kr/vr [nk,B,KV,kc,D]

    def q_block(_, qi_and_block):
        qi, qb = qi_and_block
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_block(carry, ki_and_kv):
            m, l, acc = carry
            ki, kb, vb = ki_and_kv
            k_pos = ki * kc + jnp.arange(kc)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qb, kb).astype(jnp.float32)
            s = softcap(s, cfg.logit_softcap)
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if cfg.window is not None:
                ok &= (q_pos[:, None] - k_pos[None, :]) < cfg.window
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(vb.dtype),
                                    vb).astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, groups, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, groups, qc), jnp.float32)
        a0 = jnp.zeros((b, kvh, groups, qc, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qr))
    # outs [nq,B,KV,G,qc,D] -> [B,Sq,H,D]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, d)
    out = out.astype(q.dtype)
    out = logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
    return out


def _sdpa_chunked_partial(cfg: AttnConfig, q, k, v, *, causal: bool,
                          q_offset=0, k_offset=0):
    """Chunked attention returning UNNORMALIZED partials (m, l, acc) so that
    shards holding different key ranges can be combined afterwards.
    Shapes: m,l [B,KV,G,Sq]; acc [B,KV,G,Sq,D]."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    qc = min(cfg.q_chunk, sq)
    kc = min(cfg.kv_chunk, sk)
    assert sq % qc == 0 and sk % kc == 0
    nq, nk = sq // qc, sk // kc
    qr = q.reshape(b, nq, qc, kvh, groups, d).transpose(1, 0, 3, 4, 2, 5)
    kr = k.reshape(b, nk, kc, kvh, d).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(b, nk, kc, kvh, d).transpose(1, 0, 3, 2, 4)

    def q_block(_, qi_and_block):
        qi, qb = qi_and_block
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_block(carry, ki_and_kv):
            m, l, acc = carry
            ki, kb, vb = ki_and_kv
            k_pos = k_offset + ki * kc + jnp.arange(kc)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qb, kb).astype(jnp.float32)
            s = softcap(s, cfg.logit_softcap)
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if cfg.window is not None:
                ok &= (q_pos[:, None] - k_pos[None, :]) < cfg.window
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(vb.dtype),
                                    vb).astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, groups, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, groups, qc), jnp.float32)
        a0 = jnp.zeros((b, kvh, groups, qc, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), kr, vr))
        return None, (m, l, acc)

    _, (m, l, acc) = jax.lax.scan(q_block, None, (jnp.arange(nq), qr))
    # [nq,B,KV,G,qc(,D)] -> [B,KV,G,Sq(,D)]
    m = m.transpose(1, 2, 3, 0, 4).reshape(b, kvh, groups, sq)
    l = l.transpose(1, 2, 3, 0, 4).reshape(b, kvh, groups, sq)
    acc = acc.transpose(1, 2, 3, 0, 4, 5).reshape(b, kvh, groups, sq, d)
    return m, l, acc


def _sdpa_seq_parallel(cfg: AttnConfig, q, k, v, *, causal: bool,
                       axis: str = "model"):
    """Sequence-parallel flash attention (ring/flash-decoding style, adapted):
    keys/values are sharded along seq over the `axis` mesh dimension; every
    shard runs chunked attention against its local KV range and the partial
    softmax statistics are combined with one pmax + two psums —
    O(B*H*Sq*D) collective bytes instead of the O(S^2) score psums that
    head_dim-sharded naive attention incurs. Queries are replicated over
    `axis` (their all-gather is inserted once by GSPMD at entry)."""
    mesh = jax.sharding.get_abstract_mesh()
    if axis not in mesh.shape or k.shape[1] % mesh.shape[axis] != 0:
        return _sdpa_chunked(cfg, q, k, v, causal=causal)
    n_shards = mesh.shape[axis]
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    local_sk = k.shape[1] // n_shards

    def inner(q, k_l, v_l):
        k_off = jax.lax.axis_index(axis) * local_sk
        m, l, acc = _sdpa_chunked_partial(cfg, q, k_l, v_l, causal=causal,
                                          k_offset=k_off)
        m_g = jax.lax.pmax(m, axis)
        scale = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * scale, axis)
        acc_g = jax.lax.psum(acc * scale[..., None], axis)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        # [B,KV,G,Sq,D] -> [B,Sq,H,D]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)

    out = jax.shard_map(
        inner,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None)),
        out_specs=P(), axis_names={axis}, check_vma=False)(q, k, v)
    out = out.astype(q.dtype)
    return logical_constraint(out, ("batch", "seq", "heads", "head_dim"))


def _sdpa_dispatch(cfg: AttnConfig, q, k, v, *, causal: bool,
                   q_offset=0):
    if cfg.impl == "seq_parallel":
        return _sdpa_seq_parallel(cfg, q, k, v, causal=causal)
    if cfg.impl == "chunked":
        return _sdpa_chunked(cfg, q, k, v, causal=causal, q_offset=q_offset)
    sq, sk = q.shape[1], k.shape[1]
    mask = (causal_mask(sq, sk, cfg.window) if causal
            else jnp.ones((1, sq, sk), bool))
    return _sdpa(cfg, q, k, v, mask)


def causal_mask(sq: int, sk: int, window: int | None = None) -> jax.Array:
    i = jnp.arange(sq)[:, None]
    j = jnp.arange(sk)[None, :]
    m = j <= i
    if window is not None:
        m &= (i - j) < window
    return m[None]                                    # [1, Sq, Sk]


def attention_train(p, cfg: AttnConfig, x, *, kv_x=None, causal=True):
    """Full-sequence attention (train / encoder)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, kv_x)
    if cfg.use_rope and kv_x is None:      # cross-attention carries no rope
        pos = jnp.arange(s)
        sin, cos = rope_table(pos, cfg.head_dim, cfg.rope_theta)
        q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
    return _proj_out(p, cfg, _sdpa_dispatch(cfg, q, k, v, causal=causal))


# ---------------------------------------------------------------------------
# Caching (prefill / decode)
# ---------------------------------------------------------------------------

def init_cache(cfg: AttnConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    s = min(max_seq, cfg.window) if cfg.window is not None else max_seq
    shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
    axes = ("batch", "seq", "kv_heads", "head_dim")
    return ({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)},
            {"k": axes, "v": axes})


def attention_prefill(p, cfg: AttnConfig, x, cache):
    """Run full attention over the prompt and fill the cache."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    if cfg.use_rope:
        pos = jnp.arange(s)
        sin, cos = rope_table(pos, cfg.head_dim, cfg.rope_theta)
        q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
    out = _proj_out(p, cfg, _sdpa_dispatch(cfg, q, k, v, causal=True))

    w = cache["k"].shape[1]
    if cfg.window is not None and s >= w:              # keep the last w entries
        k_in, v_in = k[:, s - w:], v[:, s - w:]
        new_cache = {"k": k_in.astype(cache["k"].dtype),
                     "v": v_in.astype(cache["v"].dtype)}
        # ring alignment: position t sits in slot t % w; roll so that holds
        shift = jnp.asarray((s - w) % w)
        new_cache = {n: jnp.roll(c, shift, axis=1) for n, c in new_cache.items()}
    else:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    return out, new_cache


def attention_decode(p, cfg: AttnConfig, x, cache, pos):
    """One-token decode step. x [B, 1, d]; pos scalar int32 (position of x)."""
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x)                          # [B,1,H,D]
    if cfg.use_rope:
        sin, cos = rope_table(pos[None], cfg.head_dim, cfg.rope_theta)
        q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)

    s_cache = cache["k"].shape[1]
    if cfg.window is not None:
        slot = pos % s_cache
    else:
        slot = pos
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))

    idx = jnp.arange(s_cache)
    if cfg.window is not None:
        slot_pos = pos - ((pos - idx) % s_cache)       # position stored per slot
        mask = (slot_pos >= 0)[None, None, :]
    else:
        mask = (idx <= pos)[None, None, :]
    out = _sdpa(cfg, q, ck.astype(q.dtype), cv.astype(q.dtype),
                jnp.broadcast_to(mask, (b, 1, s_cache)))
    return _proj_out(p, cfg, out), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------

def init_cross_cache(cfg: AttnConfig, p, enc_out, dtype=jnp.bfloat16):
    """Precompute encoder-side k/v once per request."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.use_bias:
        k, v = k + p["bk"], v + p["bv"]
    return {"k": k.astype(dtype), "v": v.astype(dtype)}


def cross_attention_step(p, cfg: AttnConfig, x, cross_cache):
    """Decoder query over fixed encoder kv (any Sq, full visibility)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]) * cfg.scale
    if cfg.use_bias:
        q = q + p["bq"]
    k, v = cross_cache["k"].astype(q.dtype), cross_cache["v"].astype(q.dtype)
    mask = jnp.ones((1, q.shape[1], k.shape[1]), bool)
    return _proj_out(p, cfg, _sdpa(cfg, q, k, v, mask))


# ---------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    num_heads: int
    kv_lora: int = 512
    q_lora: int = 1536                 # 0: queries straight from wq
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    logit_softcap: float | None = None
    latent_norm: bool = False          # RMSNorm on each latent
    rope_scaling: tuple | None = None  # YaRN, as (key, value) pairs

    @property
    def scale(self) -> float:
        return ((self.qk_nope + self.qk_rope) ** -0.5
                * yarn_softmax_scale(self.rope_scaling))


def init_mla(ini: Initializer, cfg: MLAConfig):
    d, h = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope + cfg.qk_rope
    if cfg.q_lora:
        p = {"q_down": ini.fan_in((d, cfg.q_lora), ("embed", "kv_lora")),
             "q_up": ini.fan_in((cfg.q_lora, h, qk),
                                ("kv_lora", "heads", "head_dim"))}
    else:
        p = {"wq": ini.fan_in((d, h, qk), ("embed", "heads", "head_dim"))}
    p.update({
        "kv_down": ini.fan_in((d, cfg.kv_lora), ("embed", "kv_lora")),
        "k_rope": ini.fan_in((d, cfg.qk_rope), ("embed", "qk_rope")),
        "k_up": ini.fan_in((cfg.kv_lora, h, cfg.qk_nope),
                           ("kv_lora", "heads", "head_dim")),
        "v_up": ini.fan_in((cfg.kv_lora, h, cfg.v_dim),
                           ("kv_lora", "heads", "head_dim")),
        "wo": ini.fan_in((h, cfg.v_dim, d), ("heads", "head_dim", "embed"),
                         in_dim_idx=1),
    })
    if cfg.latent_norm:
        p["kv_norm"] = {"scale": ini.zeros((cfg.kv_lora,), ("kv_lora",))}
        if cfg.q_lora:
            p["q_norm"] = {"scale": ini.zeros((cfg.q_lora,), ("kv_lora",))}
    return p


def _mla_qc(p, cfg: MLAConfig, x, positions):
    """Queries + latent (c_kv, k_rope) for a block of tokens."""
    if cfg.q_lora:
        c_q = jnp.einsum("bsd,dl->bsl", x, p["q_down"])
        if cfg.latent_norm:
            c_q = rmsnorm(p["q_norm"], c_q)
        q = jnp.einsum("bsl,lhk->bshk", c_q, p["q_up"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :cfg.qk_nope], q[..., cfg.qk_nope:]
    c_kv = jnp.einsum("bsd,dl->bsl", x, p["kv_down"])
    if cfg.latent_norm:
        c_kv = rmsnorm(p["kv_norm"], c_kv)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["k_rope"])
    sin, cos = rope_table(positions, cfg.qk_rope, cfg.rope_theta,
                          cfg.rope_scaling)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_train(p, cfg: MLAConfig, x):
    """Training-time MLA: materialize per-head k,v from the latent."""
    b, s, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, cfg, x, jnp.arange(s))
    k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["k_up"])
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["v_up"])
    scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope))
    scores = (scores * cfg.scale).astype(jnp.float32)
    scores = softcap(scores, cfg.logit_softcap)
    mask = causal_mask(s, s)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthk->bshk", probs, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def init_mla_cache(cfg: MLAConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return ({"c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora), dtype),
             "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope), dtype)},
            {"c_kv": ("batch", "seq", "kv_lora"),
             "k_rope": ("batch", "seq", "qk_rope")})


def mla_prefill(p, cfg: MLAConfig, x, cache):
    out = mla_train(p, cfg, x)
    b, s, _ = x.shape
    _, _, c_kv, k_rope = _mla_qc(p, cfg, x, jnp.arange(s))
    return out, {
        "c_kv": jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0)),
        "k_rope": jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, 0, 0)),
    }


def mla_decode(p, cfg: MLAConfig, x, cache, pos):
    """Absorbed-projection decode: attend in the 512-d latent space.

    score_h(t) = q_nope_h^T (k_up_h c_t) + q_rope_h^T k_rope_t
               = (k_up_h^T q_nope_h)^T c_t + ...
    so the per-head query is absorbed into latent space and the cache stays
    (kv_lora + qk_rope) wide — the production MLA decode trick.
    """
    b = x.shape[0]
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, cfg, x, pos[None])
    c_cache = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
    r_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, pos, 0))

    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["k_up"])    # absorb k_up
    scores = (jnp.einsum("bshl,btl->bhst", q_lat, c_cache.astype(q_lat.dtype))
              + jnp.einsum("bshk,btk->bhst", q_rope, r_cache.astype(q_rope.dtype)))
    scores = (scores * cfg.scale).astype(jnp.float32)
    scores = softcap(scores, cfg.logit_softcap)
    mask = (jnp.arange(c_cache.shape[1]) <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btl->bshl", probs, c_cache.astype(x.dtype))
    out = jnp.einsum("bshl,lhk->bshk", out_lat, p["v_up"])     # absorb v_up
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"c_kv": c_cache, "k_rope": r_cache}
