"""Public pytree-level API for gradient compression.

The paper applies sparsification independently per layer (section 5.2); here a
"layer" is a pytree leaf. ``compress_tree`` splits the PRNG key per leaf,
compresses each, and aggregates accounting. Error feedback (beyond-paper,
Seide et al. 2014 / Alistarh et al. 2018) threads a per-worker residual tree
through both the dense and the sparse (``compress_tree_sparse``) paths; it is
required for the biased top-k baseline and an optional add-on for any
sparsifying scheme. A config that asks for error feedback without residual
state raises — the flag is never a silent no-op.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import coding
from repro.core import schemes as schemes_lib
from repro.core._compressors import CompressedGrad, make_compressor
from repro.core.grouping import plan_tree


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Static configuration for the gradient-compression stage.

    ``name`` is a selector ∘ codec composition: a bare selector
    (``"gspar"``, ``"unisp"``, ``"topk"``, ``"bernoulli"``,
    ``"identity"``) defaults to the float codec, ``"selector+codec"``
    (``"gspar+qsgd8"``, ``"unisp+bf16"``, ``"topk+ternary"``) names both
    stages, and the legacy monolithic names keep working as aliases:
    ``"qsgd"`` = identity∘qsgd<qsgd_bits>, ``"terngrad"`` =
    bernoulli∘ternary, ``"none"`` = identity∘f32.

    Every composition travels on every wire. The old dense-only ban on
    qsgd/terngrad is replaced by per-composition capacity rules: the sparse
    wires size their buffers from the *selector* (``k_cap = ceil(slack *
    rho * d)`` for the rho-targeting selectors; the full ``d`` for
    bernoulli/identity, whose expected nnz is data-dependent and unbounded
    — the only static capacity that cannot silently truncate them into a
    biased average).

    On the sparse wires each leaf's bucket layout (``wire_layout``) is
    chosen statically per leaf from ``(k_cap, d)`` and the codec wire
    width: COO index list, packed occupancy bitmap, index-elided dense
    value run, or Golomb-Rice delta-coded index stream (wire-format v3,
    shipped via a two-phase exchange) — whichever realizes the fewest wire
    bytes (the section-3.3 shorter-branch rule on the actual collective,
    with RICE entering at its worst-case capacity so realized bytes only
    undercut the choice; see repro.comm.wire_layout). ``"auto"`` is that
    argmin; a concrete name forces one layout everywhere.

    ``exchange`` picks how the sparse wires realize their collectives:
    ``"sync"`` is the classic end-of-step barrier (one concatenated
    coordinate space, one all_gather set per wire-dtype bucket, RICE
    counts on a separate phase-one collective); ``"overlap"`` restructures
    the exchange into per-bucket fused word streams issued in
    reverse-backward leaf order — each bucket's single collective starts
    as soon as its leaves are packed, with RICE's phase-one counts riding
    in-band at a static header offset (see repro.comm.sync). Both modes
    are bit-identical and charge identical wire bytes; ``exchange`` only
    changes collective structure and issue order. ``overlap_bucket_bytes``
    caps one overlapped bucket's payload (smaller = more buckets = finer
    comm/compute pipelining on a real interconnect).

    ``xla_preset`` names an XLA comm-tuning preset
    (repro.comm.xla_flags): flag sets that make the overlapped issue
    order actually overlap in the compiled schedule (async collectives,
    latency-hiding scheduler). The launchers apply it to XLA_FLAGS before
    backend init; the config only records/validates the choice.

    Invalid combinations (e.g. error feedback on the residual-free
    identity∘f32) raise here, at construction time — never silently
    degrade at run time.
    """
    name: str = "gspar"              # selector[+codec] composition or legacy alias
    rho: float = 0.1                 # target density (gspar-greedy, unisp, topk)
    eps: float = 1.0                 # variance budget (gspar-closed)
    algo: str = "greedy"             # gspar solver: greedy | closed
    num_iters: int = 2               # greedy rescale iterations (paper uses 2)
    qsgd_bits: int = 4
    float_bits: int = 32             # b in the coding model
    codec: str | None = None         # value codec; None -> from name, else f32
    error_feedback: bool = False     # accumulate compression residual locally
    min_leaf_size: int = 256         # leaves smaller than this are sent dense
    # backend selection (consumed by repro.core.sparse)
    backend: str = "auto"            # auto | reference | pallas
    # wire/sync settings (consumed by repro.comm)
    wire: str = "dense"              # dense | gather | packed
    wire_layout: str = "auto"        # auto | coo | bitmap | dense | rice —
                                     # per-leaf bucket layout
                                     # (repro.comm.wire_layout); auto = min
                                     # realized bytes per leaf
    capacity_slack: float = 1.25     # k_cap slack over the selector's rho target
    resparsify_pods: bool = False    # Alg.1 step 7 -> hierarchical pod-level resync
    exchange: str = "sync"           # sync | overlap — sparse collective structure
    overlap_bucket_bytes: int = 1 << 20  # payload cap per overlapped bucket
    bucket_coord_cap: int = 2**31 - 1    # coords per sparse wire chunk: buckets
                                     # past this split into multiple collectives
                                     # (plan-level chunking, repro.core.grouping.
                                     # chunk_spans); the default is the int32
                                     # scatter-index ceiling
    xla_preset: str = "none"         # XLA comm-tuning preset (repro.comm.xla_flags)
    # adaptive control loop (consumed by repro.comm.sync via ControlState)
    adaptive: bool = False           # thread ControlState through sync_tree:
                                     # delta transmission vs the last-sent
                                     # EMA + LASG-style per-leaf skipping;
                                     # requires error_feedback (skipped
                                     # deltas fold into the residual)
    delta_beta: float = 1.0          # last-sent EMA weight: the wire carries
                                     # g - beta * last_sent (0 disables delta
                                     # coding even when adaptive)
    skip_tau: float = 0.0            # skip a leaf when ||delta + residual||^2
                                     # <= tau * tracked bound (0 = never skip)
    bound_decay: float = 0.9         # EMA decay of the per-leaf energy bound
    rice_fitted: bool = False        # wire-format v4: fit the Golomb-Rice
                                     # parameter per layer per step and ship
                                     # it in the phase-one counts header
    density_gain: float = 1.0        # agspar: rho_eff = clip(gain * s/d, ...)
    density_floor: float = 0.1       # agspar: rho_eff >= floor * rho

    def __post_init__(self):
        if self.wire not in ("dense", "gather", "packed"):
            raise ValueError(f"unknown wire format {self.wire!r} "
                             "(valid: 'dense', 'gather', 'packed')")
        if self.exchange not in ("sync", "overlap"):
            raise ValueError(f"unknown exchange mode {self.exchange!r} "
                             "(valid: 'sync', 'overlap')")
        if self.overlap_bucket_bytes < 4:
            raise ValueError(
                f"overlap_bucket_bytes={self.overlap_bucket_bytes} is below "
                "one int32 word; the overlapped exchange cannot ship a "
                "zero-byte bucket (valid: any int >= 4)")
        if not 1 <= self.bucket_coord_cap <= 2**31 - 1:
            raise ValueError(
                f"bucket_coord_cap={self.bucket_coord_cap} is outside the "
                f"int32 coordinate space (valid: 1 <= cap <= {2**31 - 1}); "
                "sparse wire chunks scatter with int32 coordinates, so a "
                "chunk can never span more")
        from repro.comm.xla_flags import PRESETS   # leaf module, no cycle
        if self.xla_preset not in PRESETS:
            raise ValueError(f"unknown xla_preset {self.xla_preset!r} "
                             f"(valid: {tuple(sorted(PRESETS))})")
        if self.wire_layout not in ("auto", "coo", "bitmap", "dense",
                                    "rice"):
            raise ValueError(f"unknown wire layout {self.wire_layout!r} "
                             "(valid: 'auto', 'coo', 'bitmap', 'dense', "
                             "'rice')")
        if not 0.0 <= self.delta_beta <= 1.0:
            raise ValueError(f"delta_beta={self.delta_beta} outside [0, 1]; "
                             "the last-sent EMA weight is a convex mixing "
                             "coefficient")
        if self.skip_tau < 0.0:
            raise ValueError(f"skip_tau={self.skip_tau} is negative; the "
                             "skip threshold scales a squared norm (valid: "
                             ">= 0, 0 disables skipping)")
        if not 0.0 <= self.bound_decay < 1.0:
            raise ValueError(f"bound_decay={self.bound_decay} outside "
                             "[0, 1); the energy bound is an EMA and decay "
                             "1 would never incorporate new steps")
        if not 0.0 < self.density_gain <= 1.0:
            raise ValueError(
                f"density_gain={self.density_gain} outside (0, 1]; gain > 1 "
                "would let the fitted density exceed the static rho ceiling "
                "the wire capacity is sized from")
        if not 0.0 <= self.density_floor <= 1.0:
            raise ValueError(f"density_floor={self.density_floor} outside "
                             "[0, 1]; it is a fraction of the static rho")
        if self.adaptive:
            if not self.error_feedback:
                raise ValueError(
                    "adaptive=True requires error_feedback=True: a skipped "
                    "leaf's delta and the delta-coding closure both fold "
                    "into the EF residual; without it the control loop "
                    "would silently drop gradient mass.")
            if self.resparsify_pods:
                raise ValueError(
                    "adaptive=True with resparsify_pods=True is not "
                    "supported: the pod-stage recompression re-selects "
                    "coordinates after the control loop's delta/skip "
                    "decisions, which breaks the last-sent bookkeeping. "
                    "Use the single-stage pod sync (resparsify_pods=False).")
        scheme = self.scheme()       # raises on unknown selector/codec/algo
        if self.name.split("+")[0] == "gspar" \
                and self.algo not in ("greedy", "closed"):
            raise ValueError(f"unknown gspar algo {self.algo!r} "
                             "(valid: 'greedy', 'closed')")
        if self.error_feedback:
            if scheme.selector.name == "identity" \
                    and not (scheme.codec.rounds_values
                             or scheme.codec.integer_coded):
                raise ValueError(
                    f"unsupported (scheme, error_feedback) pair "
                    f"({self.name!r}, True): identity selection with a "
                    "lossless codec has zero residual; error feedback "
                    "would be a silent no-op. Valid with error feedback: "
                    "any sparsifying selector ('gspar', 'unisp', 'topk', "
                    "'bernoulli'), or identity composed with a rounding "
                    "codec ('bf16', 'qsgd<bits>', 'ternary').")

    def scheme(self) -> schemes_lib.Scheme:
        """The resolved selector ∘ codec composition (cached per config —
        capacity()/compress paths resolve once per CompressionConfig, not
        once per leaf).

        The wire may upgrade the codec: ``wire='packed'`` with the default
        float codec rides bf16 values (the pre-refactor packed transform);
        an explicitly named codec wins over the upgrade.
        """
        return _resolve_scheme(self)

    def capacity(self, d: int) -> int:
        """Scheme-aware static sparse-wire capacity for a leaf of size d."""
        return self.scheme().selector.capacity(d, self.capacity_slack)

    def describe(self) -> str:
        """One-line human summary of the resolved configuration — what the
        launchers print at startup and the sweep drivers use as labels.
        Only settings that are active for this config appear (e.g. no
        wire-layout/exchange noise for the dense wire)."""
        parts = [self.scheme().name, f"rho={self.rho:g}",
                 f"wire={self.wire}"]
        if self.wire != "dense":
            parts += [f"layout={self.wire_layout}",
                      f"exchange={self.exchange}"]
            if self.bucket_coord_cap != 2**31 - 1:
                parts.append(f"coord_cap={self.bucket_coord_cap}")
        parts.append(f"backend={self.backend}")
        if self.error_feedback:
            parts.append("ef")
        if self.adaptive:
            parts.append(f"adaptive(beta={self.delta_beta:g}"
                         f" tau={self.skip_tau:g}"
                         f" decay={self.bound_decay:g})")
        if self.rice_fitted:
            parts.append("rice_fitted")
        if self.resparsify_pods:
            parts.append("resparsify_pods")
        if self.xla_preset != "none":
            parts.append(f"xla={self.xla_preset}")
        return " ".join(parts)


@functools.lru_cache(maxsize=None)
def _resolve_scheme(cfg: CompressionConfig) -> schemes_lib.Scheme:
    codec = cfg.codec
    if cfg.wire == "packed" and codec is None and "+" not in cfg.name:
        _, legacy_codec = schemes_lib.parse_composition(
            cfg.name, qsgd_bits=cfg.qsgd_bits)
        if legacy_codec is None:
            codec = "bf16"
    return schemes_lib.make_scheme(
        cfg.name, codec=codec, rho=cfg.rho, eps=cfg.eps, algo=cfg.algo,
        num_iters=cfg.num_iters, qsgd_bits=cfg.qsgd_bits,
        float_bits=cfg.float_bits, density_gain=cfg.density_gain,
        density_floor=cfg.density_floor)


@dataclasses.dataclass(frozen=True)
class TreeStats:
    """Aggregated per-step compression accounting across all leaves."""
    bits: jax.Array          # total message bits this worker sends
    dense_bits: jax.Array    # what an uncompressed message would cost
    density: jax.Array       # realized nnz fraction over all coords
    var_ratio: jax.Array     # size-weighted mean ||Q(g)||^2/||g||^2


jax.tree_util.register_dataclass(TreeStats)


def compress_leaf(cfg: CompressionConfig, key: jax.Array, g: jax.Array) -> CompressedGrad:
    return cfg.scheme().compress(key, g)


def _require_residual(cfg: CompressionConfig, residual: Any | None,
                      where: str) -> None:
    if cfg.error_feedback and residual is None:
        raise ValueError(
            f"error_feedback=True but no residual state reached {where}: "
            "the compression error would be silently dropped. Thread a "
            "FeedbackState (repro.optim.optimizers.init_feedback) through "
            "the train step, or pass a zeros residual tree explicitly.")


def compress_tree(cfg: CompressionConfig, key: jax.Array, grads: Any,
                  residual: Any | None = None,
                  stacked: Any | None = None) -> tuple[Any, Any, TreeStats]:
    """Compress every leaf of ``grads``; returns (q_tree, new_residual, stats).

    If ``cfg.error_feedback`` the residual tree (same structure, REQUIRED —
    raises if absent) is added to the gradient before compression and the
    compression error ``target - Q(target)`` is returned as the new residual;
    without error feedback ``new_residual`` is None.

    ``stacked`` (optional, same structure, bool leaves) marks leaves whose
    leading axis is a scan-over-layers stack: those are compressed per layer
    (vmap over axis 0) — the paper applies sparsification independently per
    layer, and it keeps flattened sizes within int32 indexing range.
    """
    _require_residual(cfg, residual, "compress_tree")
    integer_residual = cfg.scheme().codec.integer_coded
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    res_leaves = (jax.tree_util.tree_flatten(residual)[0]
                  if residual is not None else [None] * len(leaves))
    stk_leaves = (jax.tree_util.tree_flatten(stacked)[0]
                  if stacked is not None else [False] * len(leaves))
    keys = jax.random.split(key, max(len(leaves), 1))

    none_comp = make_compressor("none", b=cfg.float_bits)   # hoisted: one
    # passthrough compressor for every tiny leaf, not one per loop iteration
    q_leaves, new_res, bits, dense_bits, nnz, total, wvar = [], [], [], [], [], [], []
    for leaf, res, k, stk in zip(leaves, res_leaves, keys, stk_leaves):
        target = leaf + res if cfg.error_feedback else leaf
        if leaf.size < cfg.min_leaf_size:     # tiny leaves: dense passthrough
            cg = none_comp(k, target)
            cg_bits, cg_var = cg.bits, cg.var_ratio
        elif stk and leaf.ndim >= 2 and leaf.shape[0] > 1:
            lk = jax.random.split(k, leaf.shape[0])
            cg = jax.vmap(lambda kk, gg: compress_leaf(cfg, kk, gg))(lk, target)
            cg_bits = jnp.sum(cg.bits)
            cg_var = jnp.mean(cg.var_ratio)
        else:
            cg = compress_leaf(cfg, k, target)
            cg_bits, cg_var = cg.bits, cg.var_ratio
        q_leaves.append(cg.q)
        if cfg.error_feedback:
            if integer_residual:
                # integer codecs (qsgd): the decode ends in an inexact
                # multiply, which XLA:CPU fma-contracts into `target - q`
                # or not depending on the surrounding fusion — the dense
                # and gather wires would then disagree on the residual by
                # an ulp. A scatter's combiner never contracts with its
                # update producer, and the sparse wires compute their
                # residual with exactly this op
                # (core.sparse._residual_from_buffers), so the identity-
                # indexed scatter keeps the two bit-identical in every
                # compilation context. Float codecs are immune (their last
                # op is a convert or an exact product) and keep the cheap
                # elementwise subtract.
                flat_t = target.reshape(-1)
                res = flat_t.at[jnp.arange(flat_t.shape[0])].add(
                    -cg.q.reshape(-1).astype(flat_t.dtype))
                new_res.append(res.reshape(leaf.shape).astype(leaf.dtype))
            else:
                new_res.append((target - cg.q).astype(leaf.dtype))
        bits.append(cg_bits)
        dense_bits.append(jnp.asarray(float(leaf.size * cfg.float_bits)))
        nnz.append(jnp.count_nonzero(cg.q).astype(jnp.float32))
        total.append(float(leaf.size))
        wvar.append(cg_var * float(leaf.size))   # leaf.size may exceed int32

    tot = sum(total)
    stats = TreeStats(
        bits=sum(bits), dense_bits=sum(dense_bits),
        density=sum(nnz) / tot,
        var_ratio=sum(wvar) / tot,
    )
    q_tree = jax.tree_util.tree_unflatten(treedef, q_leaves)
    res_tree = (jax.tree_util.tree_unflatten(treedef, new_res)
                if cfg.error_feedback else None)
    return q_tree, res_tree, stats


def zeros_like_residual(params: Any) -> Any:
    return jax.tree.map(jnp.zeros_like, params)


def _map_rows(backend, fn, gkeys: jax.Array, stack: jax.Array):
    """One compiled dispatch for a shape group's [rows, d] emit, lowered
    per the backend's preference (``Backend.batched_emit``): ``vmap`` where
    batching extends a kernel grid (pallas — one launch per group), a
    rolled ``lax.map`` where row-at-a-time keeps the working set
    cache-resident (the jnp reference on XLA:CPU — a vmapped solver
    streams the whole stack through memory once per elementwise pass,
    which measures ~1.5x slower than the rolled loop at transformer
    sizes). Both lowerings run the identical single-row computation with
    a counter-based per-row PRNG, so they are bit-identical to each other
    and to the retired per-leaf walk."""
    if backend.batched_emit:
        return jax.vmap(fn)(gkeys, stack)
    return jax.lax.map(lambda kg: fn(*kg), (gkeys, stack))


def _concat_keys(parts: list) -> jax.Array:
    """Concatenate PRNG key batches. Typed key arrays support
    ``jnp.concatenate`` on current jax; the key-data round-trip covers
    older versions where they do not."""
    if len(parts) == 1:
        return parts[0]
    try:
        return jnp.concatenate(parts)
    except TypeError:
        data = jnp.concatenate([jax.random.key_data(p) for p in parts])
        return jax.random.wrap_key_data(data,
                                        impl=jax.random.key_impl(parts[0]))


def compress_tree_sparse(cfg: CompressionConfig, key: jax.Array, grads: Any,
                         stacked: Any | None = None,
                         residual: Any | None = None):
    """Compress the tree straight into compact ``SparseGrad`` wire buffers,
    with one compiled dispatch per *shape group*, not per leaf.

    The sparse twin of ``compress_tree`` for the gather/packed wires: the
    backend emits ``(values, idx)`` directly, so the dense Q(g) layout never
    round-trips through HBM between compression and the collective. Leaves
    are grouped by ``(dtype, row length d, k_cap)`` (repro.core.grouping):
    each group stacks into one ``[rows, d]`` batch and runs the selector ∘
    codec emit as ONE compiled dispatch (``_map_rows`` — a vmapped batch on
    kernel backends, a rolled ``lax.map`` on the jnp reference) — a
    transformer tree's 30+ leaves collapse to a handful of computations
    per step.

    Per-leaf semantics are preserved exactly. Each leaf keeps its own PRNG
    key (the per-leaf split, then a per-layer split for stacked leaves,
    concatenated in member order), each row runs the same per-row selector
    math the per-leaf loop ran, and group order is first-member tree order —
    so the grouped path is bit-identical to the retired per-leaf walk on
    both backends, with and without error feedback. The dense/gather
    equivalence tests rely on this.

    With ``cfg.error_feedback`` the residual tree (same structure, REQUIRED)
    is added to each leaf before compression, and the new residual is
    computed from the compact buffers — ``target`` minus a scatter-subtract
    of ``(values, idx)``, sliced back per member row block — so the dense
    Q(g) layout still never materializes. Tiny dense-passthrough leaves
    transmit the full target, so their residual is exactly zero.

    Returns ``(items, new_residual, treedef, stats)`` where each item is a
    group-level 3-tuple:

    - ``("dense", flat, members)`` — ONE concatenated f32 passthrough of
      every tiny leaf; ``members = ((leaf_index, size), ...)`` slices it
      back per leaf.
    - ``("sparse", sg, members)`` — one stacked ``SparseGrad`` of shape
      ``[rows, k_cap]`` for a shape group; ``members = ((leaf_index,
      rows), ...)`` maps consecutive row blocks back to leaves (flat
      leaves contribute one row, stacked leaves one per layer).

    ``new_residual`` is a grads-structured tree (None without error
    feedback).
    """
    from repro.core.sparse import resolve_backend

    _require_residual(cfg, residual, "compress_tree_sparse")
    backend = resolve_backend(cfg.backend)
    ef = cfg.error_feedback
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    res_leaves = (jax.tree_util.tree_flatten(residual)[0]
                  if residual is not None else [None] * len(leaves))
    stk_leaves = (jax.tree_util.tree_flatten(stacked)[0]
                  if stacked is not None else [False] * len(leaves))
    keys = jax.random.split(key, max(len(leaves), 1))
    plan = plan_tree(cfg, leaves, stk_leaves)

    def target_of(i: int) -> jax.Array:
        return leaves[i] + res_leaves[i] if ef else leaves[i]

    items, bits, nnz, wvar = [], [], [], []
    new_res: list = [None] * len(leaves)
    for grp in plan.groups:
        if grp.kind == "dense":
            # Tiny leaves: one concatenated dense f32 passthrough. The
            # accounting the per-leaf identity compressor produced is
            # replicated in closed form: bits is the static dense coding
            # cost, var_ratio is exactly 1 on any nonzero leaf (Q == g for
            # the passthrough), and the full target is sent so the EF
            # residual is exactly zero.
            parts = []
            for i, n in grp.members:
                t32 = target_of(i).reshape(-1).astype(jnp.float32)
                parts.append(t32)
                if ef:
                    new_res[i] = jnp.zeros_like(leaves[i])
                bits.append(jnp.asarray(
                    coding.dense_coding_bits(n, cfg.float_bits), jnp.float32))
                nnz.append(jnp.count_nonzero(t32).astype(jnp.float32))
                den = jnp.sum(t32 * t32)
                wvar.append(jnp.where(den > 0, 1.0, 0.0) * float(n))
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            items.append(("dense", flat, grp.members))
            continue

        row_targets, row_keys = [], []
        for i, rows in grp.members:
            row_targets.append(target_of(i).reshape(rows, grp.d))
            row_keys.append(jax.random.split(keys[i], rows) if rows > 1
                            else keys[i:i + 1])
        stack = (row_targets[0] if len(row_targets) == 1
                 else jnp.concatenate(row_targets))
        gkeys = _concat_keys(row_keys)
        if ef:
            sg, res_rows = _map_rows(
                backend, lambda kk, gg: backend.compress_sparse_ef(
                    cfg, kk, gg, grp.k_cap), gkeys, stack)
            r0 = 0
            for i, rows in grp.members:
                leaf = leaves[i]
                new_res[i] = (res_rows[r0:r0 + rows].reshape(leaf.shape)
                              .astype(leaf.dtype))
                r0 += rows
        else:
            sg = _map_rows(backend, lambda kk, gg: backend.compress_sparse(
                cfg, kk, gg, grp.k_cap), gkeys, stack)
        sg = dataclasses.replace(sg, shape=(grp.d,))
        items.append(("sparse", sg, grp.members))
        bits.append(jnp.sum(sg.bits))
        nnz.append(jnp.sum(sg.nnz.astype(jnp.float32)))
        wvar.append(jnp.sum(sg.var_ratio) * float(grp.d))

    tot = float(sum(leaf.size for leaf in leaves))
    stats = TreeStats(
        bits=sum(bits),
        dense_bits=jnp.asarray(tot * cfg.float_bits, jnp.float32),
        density=sum(nnz) / tot, var_ratio=sum(wvar) / tot)
    res_tree = jax.tree_util.tree_unflatten(treedef, new_res) if ef else None
    return items, res_tree, treedef, stats
