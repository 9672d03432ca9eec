"""Mixture-of-Experts FFN with sort-based token dispatch.

Design notes (TPU adaptation):
  * gshard-style one-hot dispatch einsums cost O(T*E*C*d) FLOPs — 30x the
    useful compute for deepseek-v2's 160 experts. We instead sort token
    choices by expert id per batch group and gather them into a fixed
    [held, capacity] slot buffer: FLOPs stay at the held experts' slots and
    all shapes are static (token dropping beyond capacity, standard
    practice; ``capacity_factor=None`` makes each slot buffer a row long,
    so nothing is dropped).
  * Held experts (expert parallelism): the layer holds routed experts
    ``[expert_offset, expert_offset + experts_held)`` of ``num_experts``,
    routes over all of them and computes only its own experts' part of the
    result; a choice of an expert it does not hold adds nothing. The
    shared experts are computed whole.
  * The slot buffer is annotated so GSPMD inserts the all-to-all between the
    batch-sharded token layout and the expert-sharded FFN layout (expert
    parallelism over the `data`/`pod` axes in the fsdp profile).
  * Router aux: switch-style load-balance loss over all routed experts,
    over the batch or per row (``seq_aux``, DeepSeek-V2), + router z-loss.
  * Stages (``repro.core.stages``): ``route``, ``dispatch``, ``experts``,
    ``combine``; the counter ``expert_slot_fill`` is the share of the held
    experts' slot rows that hold a routed choice.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.stages import stage
from repro.dist.sharding import logical_constraint
from repro.models.common import Initializer
from repro.models.layers import gated_mlp, init_gated_mlp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_expert: int                  # hidden width of one routed expert
    num_experts: int               # routed experts the router scores
    top_k: int
    num_shared: int = 0            # deepseek-v2 shared experts
    capacity_factor: float | None = 1.25   # None: drop no choice
    act: str = "silu"
    normalize_weights: bool = True
    routed_scaling_factor: float = 1.0     # on weights not renormalised
    seq_aux: bool = False          # balance term per row, then averaged
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    experts_held: int | None = None        # None: all num_experts
    expert_offset: int = 0

    def __post_init__(self):
        if not (0 <= self.expert_offset
                and self.expert_offset + self.held <= self.num_experts):
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) lie outside the "
                f"{self.num_experts} routed experts")

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    def capacity(self, tokens_per_group: int) -> int:
        if self.capacity_factor is None:
            return tokens_per_group    # a token picks an expert at most once
        c = int(tokens_per_group * self.top_k * self.capacity_factor
                / self.num_experts) + 1
        return max(4, -(-c // 4) * 4)          # round up to a multiple of 4


def init_moe(ini: Initializer, cfg: MoEConfig):
    d, f, e, h = cfg.d_model, cfg.d_expert, cfg.num_experts, cfg.held
    p = {
        "router": ini.normal((d, e), ("embed", "experts"), stddev=d ** -0.5),
        "w_gate": ini.fan_in((h, d, f), ("experts", "embed", "expert_mlp"),
                             in_dim_idx=1),
        "w_up": ini.fan_in((h, d, f), ("experts", "embed", "expert_mlp"),
                           in_dim_idx=1),
        "w_down": ini.fan_in((h, f, d), ("experts", "expert_mlp", "embed"),
                             in_dim_idx=1),
    }
    if cfg.num_shared:
        p["shared"] = init_gated_mlp(ini, d, cfg.num_shared * cfg.d_expert)
    return p


def _balance(cfg: MoEConfig, probs, ids):
    """``aux_loss_coef * E * sum_i f_i P_i`` over all routed experts: P_i the
    mean router probability, f_i the share of top-k choices, over the batch
    or (``seq_aux``) per row and then averaged over rows."""
    axes = (1,) if cfg.seq_aux else (0, 1)
    me = jnp.mean(probs, axis=axes)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, cfg.num_experts,
                                         dtype=jnp.float32), axis=2),
                  axis=axes) / cfg.top_k
    return cfg.aux_loss_coef * cfg.num_experts * jnp.mean(
        jnp.sum(me * ce, axis=-1))


def moe_layer(p, cfg: MoEConfig, x: jax.Array):
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar, slot fill scalar).
    Batch = dispatch group. The fill is the share (0-1) of the held
    experts' slot rows that hold a routed choice."""
    b, s, d = x.shape
    k, held, off = cfg.top_k, cfg.held, cfg.expert_offset
    cap = cfg.capacity(s)
    n = s * k

    with stage("route"):
        logits = jnp.einsum("bsd,de->bse", x, p["router"],
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)                 # [B,S,k]
        if cfg.normalize_weights and k > 1:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-9)
        else:
            weights = weights * cfg.routed_scaling_factor
        aux = _balance(cfg, probs, ids)
        if cfg.z_loss_coef:
            aux += cfg.z_loss_coef * jnp.mean(
                jax.nn.logsumexp(logits, -1) ** 2)

    # ---- sort choices by expert id within each batch group
    # SCATTER-FREE dispatch/combine: GSPMD cannot partition the natural
    # buf.at[b, slots].set(...) scatter and falls back to all-gathering the
    # full token tensor (measured 258 GB/layer on deepseek-v2 train_4k; see
    # EXPERIMENTS.md section Perf iter B2). Every step below is a gather
    # (take_along_axis) over the batch-sharded axis, which partitions clean.
    with stage("dispatch"):
        ids_f = logical_constraint(ids.reshape(b, n), ("batch", None))
        order = logical_constraint(jnp.argsort(ids_f, axis=-1),
                                   ("batch", None))
        sids = jnp.take_along_axis(ids_f, order, axis=-1)      # sorted ids
        starts = jax.vmap(
            lambda row: jnp.searchsorted(row, row, side="left"))(sids)
        ranks = jnp.arange(n)[None, :] - starts                # rank in expert
        local = sids - off                                     # held index
        keep = (local >= 0) & (local < held) & (ranks < cap)
        slots = jnp.clip(local * cap + ranks, 0, held * cap - 1)
        token_of = order // k                                  # originating token
        # slot (e, c) is filled by sorted position starts_e[e] + c (if in range)
        ex = off + jnp.arange(held)
        starts_e = jax.vmap(
            lambda row: jnp.searchsorted(row, ex, side="left"))(sids)
        ends_e = jax.vmap(
            lambda row: jnp.searchsorted(row, ex, side="right"))(sids)
        p_slot = starts_e[..., None] + jnp.arange(cap)[None, None, :]
        slot_valid = p_slot < jnp.minimum(ends_e[..., None],
                                          starts_e[..., None] + cap)
        p_clip = jnp.minimum(p_slot, n - 1).reshape(b, held * cap)
        tok = jnp.take_along_axis(token_of, p_clip, axis=-1)   # [B,held*cap]
        xs = jnp.take_along_axis(x, tok[..., None], axis=1)
        xs = xs * slot_valid.reshape(b, held * cap, 1).astype(x.dtype)
        xs = xs.reshape(b, held, cap, d)
        xs = logical_constraint(xs, ("batch", "experts", None, None))  # a2a

    # ---- expert FFN (batched einsum over the held experts)
    with stage("experts"):
        act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
        h = act(jnp.einsum("becd,edf->becf", xs, p["w_gate"]))
        h = h * jnp.einsum("becd,edf->becf", xs, p["w_up"])
        h = logical_constraint(h, ("batch", "experts", None, "expert_mlp"))
        ys = jnp.einsum("becf,efd->becd", h, p["w_down"])
        ys = logical_constraint(ys, ("batch", "experts", None, None))
        shared = gated_mlp(p["shared"], x, cfg.act) if cfg.num_shared else 0

    # ---- combine (scatter-free): each choice, in (token, choice) order,
    # gathers its slot's output; weight and reduce over the k choices
    with stage("combine"):
        inv_order = jnp.argsort(order, axis=-1)                # unsort perm
        slot_c = jnp.take_along_axis(slots, inv_order, axis=-1)
        keep_c = jnp.take_along_axis(keep, inv_order, axis=-1)
        ys_flat = logical_constraint(ys.reshape(b, held * cap, d),
                                     ("batch", None, "embed"))
        y_choice = jnp.take_along_axis(ys_flat, slot_c[..., None], axis=1)
        y_choice = logical_constraint(y_choice, ("batch", None, "embed"))
        w_k = (weights.reshape(b, n) * keep_c).astype(x.dtype)  # choice-major
        y = jnp.sum((y_choice * w_k[..., None]).reshape(b, s, k, d), axis=2)
        y = logical_constraint(y + shared, ("batch", "seq", "embed"))
    fill = jnp.mean(slot_valid.astype(jnp.float32))
    return y, aux, fill


def moe_ffn(p, cfg: MoEConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar). Batch = dispatch group."""
    y, aux, _ = moe_layer(p, cfg, x)
    return y, aux
