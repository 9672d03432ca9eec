"""Sparse gradient synchronization (Algorithm 1 on a TPU mesh).

``sync_tree`` runs *inside* a shard_map region where the given data/pod mesh
axes are manual: every leaf it sees is this device's local shard of the
gradient, and cross-replica exchange is explicit ``jax.lax`` collectives.

Wire formats (CompressionConfig.wire):
  dense  -- Q(g) stays in dense layout; psum over the data axis. Models the
            algorithm exactly; communication savings are *accounted* (bits)
            but the HLO collective is still dense. Reference semantics.
  gather -- the backend (repro.core.sparse) emits fixed-capacity
            (values, idx) buffers directly; one all_gather + local
            scatter-add. The HLO collective shrinks to 2*k_cap*M words: this
            is the TPU-native realization of the paper's sparse All-Reduce.
  packed -- gather with the value codec upgraded to bf16 when the config
            names none (the pre-refactor behavior). Halves value bytes.

The value buffers travel *codec-encoded* (repro.core.codecs): bf16 halves,
int8 ternary signs or int8/int16 qsgd levels shrink them further, plus one
f32 scale per message for the integer codecs (gathered alongside, decoded
locally after the collective). Buckets are keyed by the codec wire dtype.

The sparse wires are *bucketed*: every leaf's buffers are offset into one
concatenated coordinate space and exchanged with a single all_gather pair
per wire dtype, so a tree of hundreds of small leaves costs O(1) collectives
instead of O(n_leaves). Tiny (dense-passthrough) leaves share one psum the
same way. Since the shape-bucketed compression plan (repro.core.grouping)
the items this layer consumes are already GROUP-level: each sparse entry is
one stacked ``SparseGrad`` of shape ``[rows, k_cap]`` covering every leaf of
a (dtype, d, k_cap) shape bucket, with a ``members`` map slicing the rows
back to leaves — structurally identical to the scan-stacked leaves this
layer always handled, so packing, exchange, scatter order, and wire-byte
accounting are unchanged (and byte-/bit-identical to the per-leaf item
stream they replace). Each leaf ships under its statically stamped wire layout
(repro.comm.wire_layout): int32 COO list, packed occupancy bitmap, an
index-elided dense value run, or a Golomb-Rice delta-coded index stream
(wire-format v3) — whichever realizes the fewest bytes, so full-capacity
compositions (identity∘qsgd, bernoulli∘ternary) pay zero index overhead
and low-density leaves ship entropy-coded indices. RICE streams are
variable-length, so buckets containing them run a TWO-PHASE exchange:
phase one all-gathers the per-layer encoded word counts (a tiny int32
vector — in a real ragged collective this is what sizes the receives),
phase two gathers the payload padded to its static worst-case capacity so
the HLO collective keeps a static shape under jit; wire-byte accounting
charges the true encoded lengths (plus the counts vector), never the
padding. Compression happens exactly once per leaf, in the backend — this
layer never re-discovers nonzeros from a dense array.

Exchange structure (CompressionConfig.exchange):
  sync    -- the classic end-of-step barrier (``_bucketed_sync``): one
             concatenated coordinate space per wire-dtype bucket, one
             all_gather for values + one for index words (+ tiny ones for
             RICE counts and codec scales), a single bucket-wide
             scatter-add.
  overlap -- the overlapped per-bucket exchange (``_overlapped_sync``):
             leaves are walked in REVERSE order (the backward pass
             produces the last layers' gradients first, so their buckets
             can be issued while earlier layers are still being packed)
             and grouped into buckets capped at
             ``overlap_bucket_bytes``. Each bucket ships a fused int32
             word stream -- ``[RICE counts | index words | bitcast value
             words (4-byte dtypes) | bitcast scale words]`` per leaf, at
             static offsets derivable from the LeafPlans alone -- so
             RICE's phase-one counts ride in-band at a header offset
             instead of costing a separate sequential collective, and the
             codec-scale gather folds in too. Sub-word value dtypes
             (bf16/int8) skip the bitcast packing and ride a companion
             native-dtype all_gather per bucket (the pad/reshape/bitcast
             round trip costs real copies; a plain native-dtype gather,
             like the sync barrier's value collective, does not). All
             buckets are ISSUED before any is CONSUMED:
             under an async-collective schedule (repro.comm.xla_flags)
             bucket i's gather overlaps bucket i+1's packing. Decode
             slices the static segments back out per leaf, then ONE
             scatter-add per bucket accumulates every leaf (blocks are
             disjoint, offsets applied at decode). Issue order is a
             schedule choice; the per-coordinate reduction order is
             worker-major either way, which is why overlap stays
             bit-identical to sync and to the dense psum (the
             dense-vs-gather contract). Wire-byte accounting charges
             exactly the same components as sync — value/index/count/
             scale bytes; fused-stream segments are 4-byte aligned by
             construction and the companion stream is native-dtype, so
             no padding is ever moved or charged.

Bucket chunking: a dtype bucket's concatenated coordinate space is capped
at ``CompressionConfig.bucket_coord_cap`` (default: the int32 ceiling the
scatter indices impose). When a tree's buckets would overflow it, the plan
splits them into row-granular chunks (repro.core.grouping.chunk_spans) and
each chunk ships as its own collective pair with offsets rebased to its own
coordinate space — so trees of any size ride the sparse wire, and what used
to be a trace-time ``check_bucket_coords`` abort is now just a plan decision
(``TreePlan.chunk_count``). Every leaf's buffers are packed ONCE; chunks
slice rows out of the packed streams, so chunked exchange stays
byte- and bit-identical to the unchunked one.

Multi-pod: with ``resparsify_pods`` the intra-pod average is re-sparsified
before the inter-pod exchange — exactly the optional step 7 of Algorithm 1,
mapped onto the pod axis of the mesh. The pod stage derives its RNG from the
UNFOLDED base key (folding only non-data key axes), so every data worker of
a pod re-sparsifies the identical pod average with the identical key and the
pods' messages agree bit-for-bit. With error feedback the pod stage carries
ITS OWN per-pod residual (``FeedbackState.pod_residual``, replicated across
the pod's data workers): the second compression's error is re-injected next
step exactly like the worker stage's, so hierarchical sync drops nothing.
Wire bytes are reported per stage (intra-pod vs inter-pod) as well as in
total.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.comm import compaction, wire_layout
from repro.core.api import (CompressionConfig, compress_tree,
                            compress_tree_sparse)
from repro.core.grouping import chunk_spans, member_row_flags
from repro.core.sparse import SparseGrad
from repro.core.stages import stage
from repro.optim.optimizers import ControlState, FeedbackState

Axis = str | tuple[str, ...]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SyncStats:
    """Per-step accounting for one worker's gradient synchronization."""
    bits: jax.Array              # message bits this worker sent (coding model)
    dense_bits: jax.Array        # uncompressed message bits
    wire_bytes: jax.Array        # bytes actually moved by the HLO collectives
    wire_bytes_intra: jax.Array  # ... in the intra-pod (data-axis) stage
    wire_bytes_inter: jax.Array  # ... in the inter-pod stage (0 if single pod)
    density: jax.Array           # realized nnz fraction
    var_ratio: jax.Array         # ||Q(g)||^2/||g||^2, the paper's `var`
    overflow: jax.Array          # coords dropped by fixed-capacity compaction
    skipped: jax.Array = 0.0     # leaves this worker skipped (adaptive only)


def _axis_size(axis: Axis) -> jax.Array:
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    n = 1
    for a in names:
        n = n * jax.lax.axis_size(a)
    return n


def _worker_key(key: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Independent RNG per worker: fold the linearized worker index in."""
    for a in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(a))
    return key


def _sync_leaves_dense(q_tree: Any, axis: Axis):
    with stage("exchange"):
        synced = jax.tree.map(lambda q: jax.lax.pmean(q, axis), q_tree)
    wire = sum(float(q.size * q.dtype.itemsize) for q in jax.tree.leaves(q_tree))
    return synced, wire


def _encode_det(codec, vals: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Keyless (round-to-nearest) codec encode of one compact value buffer:
    the pod-stage re-compaction is deterministic by design (like its top-k
    selection), so the stochastic codecs round deterministically here. Any
    rounding bias lands in ``_compaction_drop`` and is re-carried by EF."""
    scale = codec.scale(vals)
    return codec.encode(vals, scale, None), scale


def _compact_items(cfg: CompressionConfig, leaves: list, stk_leaves: list):
    """Fixed-capacity compaction of an already-dense (e.g. pod-averaged)
    tree: the single nonzero-selection of the inter-pod stage. Values are
    re-encoded into the configured codec's wire representation so the
    inter-pod collective moves the same dtype as the intra-pod one.
    Emits the same group-level 3-tuple items as ``compress_tree_sparse``,
    under the same cached grouping plan: one compact + encode dispatch per
    shape bucket instead of one per leaf, lowered per the backend's
    ``batched_emit`` preference exactly like the intra-pod emit (vmapped
    batch on kernel backends, rolled ``lax.map`` on the jnp reference —
    see ``repro.core.api._map_rows``)."""
    from repro.core.grouping import plan_tree
    from repro.core.sparse import resolve_backend

    scheme = cfg.scheme()
    codec = scheme.codec
    batched = resolve_backend(cfg.backend).batched_emit
    plan = plan_tree(cfg, leaves, stk_leaves)
    items = []
    for grp in plan.groups:
        if grp.kind == "dense":
            parts = [leaves[i].reshape(-1).astype(jnp.float32)
                     for i, _ in grp.members]
            items.append(("dense",
                          parts[0] if len(parts) == 1
                          else jnp.concatenate(parts), grp.members))
            continue
        stack_parts = [leaves[i].reshape(rows, grp.d)
                       for i, rows in grp.members]
        stack = (stack_parts[0] if len(stack_parts) == 1
                 else jnp.concatenate(stack_parts))
        def _compact_encode(row, _k_cap=grp.k_cap):
            with stage("compact"):
                vals, idx, nnz = compaction.compact(row, _k_cap)
                vals, scale = _encode_det(codec, vals)
            return vals, idx, nnz, scale
        vals, idx, nnz, scale = (
            jax.vmap(_compact_encode)(stack) if batched
            else jax.lax.map(_compact_encode, stack))
        leaf_dtype = leaves[grp.members[0][0]].dtype
        items.append(("sparse", SparseGrad(
            values=vals, idx=idx, nnz=nnz,
            p_sum=nnz.astype(jnp.float32),   # deterministic: E[nnz]=nnz
            bits=jnp.zeros((grp.rows,), jnp.float32),
            var_ratio=jnp.zeros((grp.rows,), jnp.float32),
            scale=scale, d=grp.d, shape=(grp.d,), codec=codec.name,
            layout=wire_layout.choose(
                grp.k_cap, grp.d,
                wire_layout.value_bits_of(codec.wire_dtype(leaf_dtype)),
                cfg.wire_layout)), grp.members))
    return items


def _compaction_drops(items: list, leaves: list) -> list:
    """What the fixed-capacity pod messages failed to carry, per leaf:
    leaf minus the scatter of the codec-decoded transmitted buffers.
    Nonzero exactly on compaction overflow — the pod-union of M workers'
    coordinates routinely exceeds one worker's k_cap — and on codec
    rounding of kept values (bf16, qsgd levels, ternary). One batched
    scatter per sparse group; dense-passthrough leaves drop nothing."""
    drops: list = [None] * len(leaves)
    for kind, payload, members in items:
        if kind == "dense":
            for i, _ in members:
                drops[i] = jnp.zeros_like(leaves[i])
            continue
        sent = jax.vmap(lambda v, ix: compaction.scatter(v, ix, payload.d))(
            payload.decode_values(), payload.idx)
        r0 = 0
        for i, rows in members:
            leaf = leaves[i]
            drop = (leaf.astype(jnp.float32).reshape(-1)
                    - sent[r0:r0 + rows].reshape(-1))
            drops[i] = drop.reshape(leaf.shape).astype(leaf.dtype)
            r0 += rows
    return drops


def _apply_skip(cfg: CompressionConfig, items: list, skip_flags: list):
    """LASG-style communication skipping, applied AFTER compression: mask
    each skipped leaf's rows out of the already-built wire buffers so the
    exchange ships (and charges) only a 1-word per-row header for them.

    Values are zeroed in place — a zero update contributes exact zeros to
    the bucket scatter-add, which keeps the sparse wires bit-identical to
    the dense path's zeroed-q psum. RICE groups are PREPACKED here (via
    ``wire_layout.pack``, in the fitted format when ``cfg.rice_fitted``)
    and their word streams and counts masked to zero per skipped row:
    both backends then ship identical all-zero streams with a zero count,
    so the realized-byte accounting (4 bytes * count) charges nothing for
    a skipped row beyond its counts-header word. The static per-row value/
    index/scale charges the exchanges add are refunded by the returned
    savings scalar: a skipped non-rice row nets exactly 4 bytes (the skip
    sentinel word — see docs/WIRE_FORMAT.md), a skipped rice row exactly
    its counts word.

    Returns ``(items, wire_savings)`` with ``wire_savings`` a traced f32
    byte total to subtract from the exchange's intra-stage charge.
    """
    codec = cfg.scheme().codec
    scale_b = 4.0 if codec.has_scale else 0.0
    savings = jnp.asarray(0.0, jnp.float32)
    out_items = []
    for kind, payload, members in items:
        if kind == "dense":
            # tiny dense-passthrough leaves never skip (their flags are
            # statically False): one psum carries them regardless
            out_items.append((kind, payload, members))
            continue
        sg = payload
        lp = wire_layout.plan(sg, fitted=cfg.rice_fitted)
        mask = member_row_flags(members, skip_flags)          # [rows] bool
        vals = jnp.where(mask[:, None], jnp.zeros_like(sg.values), sg.values)
        itemsize = jnp.dtype(sg.values.dtype).itemsize
        sg2 = dataclasses.replace(sg, values=vals)
        if lp.layout == "rice":
            with stage("pack"):
                v2d, w2d, nw = wire_layout.pack(sg2, lp)
            w2d = jnp.where(mask[:, None], 0, w2d)
            nw = jnp.where(mask, 0, nw)
            sg2 = dataclasses.replace(sg2, values=v2d, rice_words=w2d,
                                      rice_used=nw)
            per_row = float(lp.val_len * itemsize) + scale_b
        else:
            per_row = (float(lp.val_len * itemsize + lp.idx_len * 4)
                       + scale_b - 4.0)
        savings = savings + (jnp.sum(mask.astype(jnp.float32))
                             * jnp.float32(per_row))
        out_items.append((kind, sg2, members))
    return out_items, savings


def _route_span(members, r0: int, n: int, d: int, seg, pieces: dict) -> None:
    """Slice one chunk span's flat reconstruction back to leaves.

    ``seg`` holds item rows ``[r0, r0 + n)`` of one group item (``n * d``
    floats); ``members`` maps item rows to leaves. Pieces append in
    ascending row order per leaf — chunks are emitted in row order, so the
    per-leaf concatenation in ``_assemble_pieces`` reassembles each leaf
    exactly, whether it arrived whole or split across chunks."""
    m0 = 0
    for i, rows in members:
        a = max(m0, r0)
        b = min(m0 + rows, r0 + n)
        if b > a:
            pieces.setdefault(i, []).append(seg[(a - r0) * d:(b - r0) * d])
        m0 += rows


def _assemble_pieces(pieces: dict, leaves: list, out: list) -> None:
    for i, ps in pieces.items():
        leaf = leaves[i]
        flat = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
        out[i] = flat.reshape(leaf.shape).astype(leaf.dtype)


def _bucketed_sync(items: list, leaves: list, axis: Axis,
                   cfg: CompressionConfig):
    """Exchange all leaves with one collective per (kind, wire-dtype) group.

    Sparse leaves are offset into a single concatenated coordinate space
    and packed per their statically stamped wire layout
    (repro.comm.wire_layout): COO leaves contribute (values, int32
    coordinates), BITMAP leaves (coordinate-ordered values, packed
    occupancy words), DENSE leaves an index-elided value run, RICE leaves
    (coordinate-ordered values, Golomb-Rice coded index words padded to
    their static capacity). Buckets with RICE leaves first all-gather the
    per-layer encoded word counts (phase one of the two-phase exchange —
    the tiny vector that sizes a real ragged receive; here it also zeroes
    payload padding before decode and prices the realized bytes). One
    all_gather then moves the bucket's value stream, one the concatenated
    int32 index/word stream (skipped entirely when every leaf elides its
    index), then a single scatter-add in worker-major order reconstructs
    the flat bucket — bitmap rank-gathers, dense iotas, and rice gap
    prefix-sums feed the same scatter, so every layout accumulates in the
    same sequential order as the dense psum (the bit-identity contract).
    Wire bytes charge RICE leaves their true encoded lengths plus the
    counts vector — the static padding is an XLA static-shape artifact,
    not traffic a length-aware collective would move. Values travel
    codec-encoded (the
    backend already emitted the wire representation); codecs with a
    per-message scale gather the (tiny) scale vector alongside and decode
    locally after the collective, per (worker, leaf, layer) slot. Dense-
    passthrough leaves share one psum. Coordinates are int32 — one
    collective therefore addresses up to 2^31 coordinates (~8.6 GB of f32
    gradient per dtype group). Buckets past ``cfg.bucket_coord_cap`` are
    CHUNKED: the greedy row-granular split of the grouping plan
    (repro.core.grouping.chunk_spans) partitions the bucket's row blocks
    into capacity-bounded chunks, each its own all_gather set with a
    rebased coordinate space. Chunk boundaries fall on row (= layer)
    boundaries, so every chunk's scatter still accumulates worker-major
    over disjoint leaf blocks: chunked and unchunked exchanges are
    bit-identical and charge identical wire bytes — chunking only caps
    the coordinate space (and buffer size) of any single collective, so
    multi-billion-parameter trees ride the sparse wire without the int32
    guard aborting the trace.
    """
    m = _axis_size(axis)
    codec = cfg.scheme().codec
    out: list = [None] * len(leaves)
    wire = 0.0
    overflow = jnp.asarray(0, jnp.int32)

    dense_ids: list = []
    sparse_groups: dict = {}
    for e, (kind, payload, _members) in enumerate(items):
        if kind == "dense":
            dense_ids.append(e)
        else:
            sparse_groups.setdefault(jnp.dtype(payload.values.dtype),
                                     []).append(e)

    if dense_ids:
        # one f32 psum for all tiny leaves: f32 keeps the mean exact for
        # low-precision leaves, and the accounting charges what the HLO
        # collective actually moves (4 bytes/element). The payloads are
        # already concatenated per group; member runs slice them back.
        with stage("pack"):
            flat = jnp.concatenate([items[e][1].reshape(-1)
                                    .astype(jnp.float32) for e in dense_ids])
        with stage("exchange"):
            synced = jax.lax.pmean(flat, axis)
        off = 0
        with stage("apply"):
            for e in dense_ids:
                for i, n in items[e][2]:
                    leaf = leaves[i]
                    out[i] = (synced[off:off + n].reshape(leaf.shape)
                              .astype(leaf.dtype))
                    off += n
        wire += float(flat.size * 4)

    cap = min(cfg.bucket_coord_cap, compaction.INT32_COORD_LIMIT)
    for wdt, ids in sorted(sparse_groups.items(), key=lambda kv: str(kv[0])):
        # pack every item ONCE (chunks row-slice the shared streams), then
        # split the bucket's row blocks into capacity-bounded chunks
        packed: dict = {}
        with stage("pack"):
            for e in ids:
                sg = items[e][1]
                lp = wire_layout.plan(sg, fitted=cfg.rice_fitted)
                # [L, val_len], [L, idx_len], [L] realized rice words
                packed[e] = (lp,) + wire_layout.pack(sg, lp) + (
                    jnp.asarray(sg.scale, jnp.float32).reshape(-1)
                    if codec.has_scale else None,)
                overflow = overflow + jnp.sum(sg.overflow())
        chunks = chunk_spans([(e, packed[e][0].layers, packed[e][0].d)
                              for e in ids], cap)
        pieces: dict = {}                # leaf id -> flat row-order pieces
        for chunk in chunks:
            vals_parts, widx_parts, scale_parts, slot_parts = [], [], [], []
            count_parts: list = []       # realized RICE words per layer
            static_idx_words = 0         # fixed-layout index words
            plans: list = []             # (item id, span LeafPlan, span r0,
            coord_off = 0                #  v_off, i_off, coord_off, c_off) —
            v_off = 0                    #  the chunk's static
            i_off = 0                    #  self-description
            s_off = 0
            c_off = 0
            with stage("pack"):
                for e, r0, n in chunk:
                    lp0, v2d, w2d, nw, sflat = packed[e]
                    lp = dataclasses.replace(lp0, layers=n)
                    w2 = w2d[r0:r0 + n]
                    if lp.layout == "coo":
                        # only coordinate lists get the chunk offset (rebased
                        # per chunk); bitmap/rice words are opaque bit payload
                        # and dense runs ship no index
                        w2 = (w2 + (jnp.arange(n, dtype=jnp.int32)
                                    * lp.d)[:, None] + jnp.int32(coord_off))
                    if lp.idx_len:
                        widx_parts.append(w2.reshape(-1))
                    if lp.layout == "rice":
                        count_parts.append(nw[r0:r0 + n])
                    else:
                        static_idx_words += n * lp.idx_len
                    vals_parts.append(v2d[r0:r0 + n].reshape(-1))
                    if codec.has_scale:
                        slot_parts.append(
                            jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                                       lp.val_len) + jnp.int32(s_off))
                        scale_parts.append(sflat[r0:r0 + n])
                    plans.append((e, lp, r0, v_off, i_off, coord_off, c_off))
                    v_off += n * lp.val_len
                    i_off += n * lp.idx_len
                    coord_off += lp.block
                    s_off += n
                    c_off += n if lp.layout == "rice" else 0
            # the chunker bounded this by construction; a trip here means a
            # caller fed spans wider than the cap past it
            compaction.check_bucket_coords(coord_off, len(chunk))
            if count_parts:
                # phase one of the two-phase exchange: the per-layer encoded
                # word counts of every RICE stream in this chunk. A real
                # ragged collective sizes its receives from exactly this
                # vector; the static-shape emulation below uses it to zero
                # payload padding pre-decode and to price realized bytes.
                with stage("pack"):
                    counts_flat = jnp.concatenate(count_parts)   # [R]
                with stage("exchange"):
                    gcounts = jax.lax.all_gather(counts_flat, axis,
                                                 tiled=False)    # [m, R]
                wire += float(counts_flat.size * 4)              # the vector
                # fitted counts carry the parameter header in their high
                # bits (wire-format v4); only the used-word field is
                # payload. The mask is identity on static-format counts.
                wire = wire + 4.0 * jnp.sum(
                    counts_flat
                    & compaction.RICE_HDR_USED_MASK).astype(jnp.float32)
            else:
                gcounts = None
            with stage("pack"):
                vals_flat = jnp.concatenate(vals_parts)
            with stage("exchange"):
                gvals = jax.lax.all_gather(vals_flat, axis,
                                           tiled=False)           # [m, V]
            if widx_parts:
                # phase two: the index/word payload at its static shape —
                # for RICE segments only the true encoded words (charged
                # above) are protocol bytes, the rest is zero padding
                with stage("pack"):
                    widx_flat = jnp.concatenate(widx_parts)
                with stage("exchange"):
                    gwidx = jax.lax.all_gather(widx_flat, axis,
                                               tiled=False)       # [m, I]
                wire += float(static_idx_words * 4)
            else:
                gwidx = None             # every leaf elided its index stream
            if codec.has_scale:
                # per-message scales ride a third (tiny: one f32 per
                # leaf/layer) all_gather; each slot decodes with its own
                # worker's scale.
                with stage("pack"):
                    scales_flat = jnp.concatenate(scale_parts)   # [S]
                    slot_map = jnp.concatenate(slot_parts)       # [V]
                with stage("exchange"):
                    gscales = jax.lax.all_gather(scales_flat, axis,
                                                 tiled=False)    # [m, S]
                with stage("decode"):
                    decoded = codec.decode(gvals, gscales[:, slot_map])
                wire += float(scales_flat.size * 4)
            else:
                with stage("decode"):
                    decoded = gvals.astype(jnp.float32)
            upd_parts, coord_parts = [], []
            with stage("decode"):
                for (e, lp, r0, v0, i0, c0, cc0) in plans:
                    dv = decoded[:, v0:v0 + lp.layers * lp.val_len]
                    wseg = (gwidx[:, i0:i0 + lp.layers * lp.idx_len]
                            if lp.idx_len else None)
                    wcnt = (gcounts[:, cc0:cc0 + lp.layers]
                            if lp.layout == "rice" else None)
                    upd, crd = wire_layout.unpack_gathered(lp, dv, wseg, c0,
                                                           wcounts=wcnt)
                    upd_parts.append(upd)
                    coord_parts.append(crd)
            with stage("apply"):
                dense = jnp.zeros((coord_off,), jnp.float32)
                dense = dense.at[
                    jnp.concatenate(coord_parts, axis=1).reshape(-1)].add(
                    jnp.concatenate(upd_parts, axis=1).reshape(-1),
                    mode="drop") / m
                for (e, lp, r0, _, _, c0, _) in plans:
                    _route_span(items[e][2], r0, lp.layers, lp.d,
                                dense[c0:c0 + lp.block], pieces)
            wire += float(v_off) * wdt.itemsize
        with stage("apply"):
            _assemble_pieces(pieces, leaves, out)

    return out, wire, overflow


def _words_of(n_elems: int, dtype) -> int:
    """int32 words needed to carry ``n_elems`` of ``dtype`` (word-aligned)."""
    return -(-n_elems * jnp.dtype(dtype).itemsize // 4)


def _word_pack(x: jax.Array) -> jax.Array:
    """Bitcast any wire-dtype buffer into a flat int32 word stream.
    Sub-word dtypes (bf16/int16: 2 per word, int8: 4 per word) are
    zero-padded to a word multiple; the pad is alignment, not payload,
    and is never charged to wire bytes."""
    flat = x.reshape(-1)
    per = 4 // jnp.dtype(flat.dtype).itemsize
    if per > 1:
        pad = (-flat.shape[0]) % per
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        flat = flat.reshape(-1, per)
    return jax.lax.bitcast_convert_type(flat, jnp.int32)


def _word_unpack(words: jax.Array, dtype, n_elems: int) -> jax.Array:
    """Inverse of ``_word_pack`` on a gathered ``[m, W]`` segment:
    ``[m, n_elems]`` in the wire dtype, alignment padding sliced off."""
    out = jax.lax.bitcast_convert_type(words, jnp.dtype(dtype))
    m = words.shape[0]
    return out.reshape(m, -1)[:, :n_elems]


def _overlapped_sync(items: list, leaves: list, axis: Axis,
                     cfg: CompressionConfig):
    """Overlapped drop-in for ``_bucketed_sync``: same arguments, same
    returns, bit-identical outputs, identical wire-byte accounting —
    different collective structure (see the module docstring).

    Sparse entries (shape groups since the grouped compression plan — each
    covers every leaf of one (dtype, d, k_cap) bucket) are walked in
    reverse order, split into capacity-bounded row spans where their
    coordinate block exceeds ``cfg.bucket_coord_cap`` (the same
    row-granular rule as the sync barrier's chunked buckets —
    repro.core.grouping.chunk_spans; a span is the atomic unit and is
    never split), and greedily packed into buckets of at most
    ``cfg.overlap_bucket_bytes`` payload AND ``bucket_coord_cap``
    coordinates. Each bucket's entry streams concatenate into ONE int32
    all_gather:

        entry stream = [counts (rice, layers words)]
                      [index words (layers*idx_len; coo pre-offset by
                       its layer strides — each leaf scatters into its
                       OWN block, so no cross-leaf coordinate space)]
                      [value words (4-byte dtypes only: f32/int32
                       bitcast in place — shape-preserving, free)]
                      [scale words (has_scale codecs, layers words)]

    Sub-word value dtypes (bf16/int16/int8) do NOT bitcast into the word
    stream — the pad/reshape/bitcast round trip materializes real copies.
    They ride a COMPANION all_gather per bucket in their native dtype
    (all sparse leaves share one codec, hence one wire dtype), exactly
    like the sync barrier's value collective but scoped to the bucket.

    Every segment offset is a trace-time constant from the LeafPlan, so
    the receiver needs no handshake: RICE word counts are read from the
    in-band header (still decode-authoritative — they zero the capacity
    padding before rice_decode exactly like the phase-one vector did),
    values are codec-decoded with their own worker's scale, and one
    scatter-add per bucket accumulates every leaf (blocks disjoint,
    offsets applied at decode) in worker-major order — the same
    per-coordinate reduction order as ``_bucketed_sync`` and the dense
    psum, which is what keeps all three bit-identical.
    """
    m = _axis_size(axis)
    codec = cfg.scheme().codec
    out: list = [None] * len(leaves)
    wire = 0.0
    overflow = jnp.asarray(0, jnp.int32)

    dense_ids = [e for e, (kind, _, _) in enumerate(items) if kind == "dense"]
    sparse_ids = [e for e, (kind, _, _) in enumerate(items)
                  if kind == "sparse"]

    # --- pack + issue, reverse-backward order ---------------------------
    # buckets: list of (segs, stream, vstream|None) where segs =
    # [(item id, span LeafPlan, span row start, word offset, fused value
    #   word count, wire dtype, companion-stream element offset)] —
    # vwords > 0 means the values are bitcast into the word stream
    # (4-byte dtypes), velems0 >= 0 means they ride the companion
    # native-dtype stream. The atomic unit is one capacity-bounded row
    # SPAN of an item (repro.core.grouping.chunk_spans): items whose
    # coordinate block exceeds ``cfg.bucket_coord_cap`` split across
    # buckets instead of aborting the trace, and a bucket flushes when
    # EITHER the byte cap or the coordinate cap would overflow (the byte
    # cap alone does not bound the coordinate space — e.g. RICE at 1%
    # density packs ~100x more coordinates than bytes).
    buckets: list = []
    cur_parts: list = []
    cur_vparts: list = []
    cur_segs: list = []
    cur_words = cur_velems = cur_coords = 0
    cap_bytes = max(4, cfg.overlap_bucket_bytes)
    cap = min(cfg.bucket_coord_cap, compaction.INT32_COORD_LIMIT)

    def flush():
        nonlocal cur_parts, cur_vparts, cur_segs
        nonlocal cur_words, cur_velems, cur_coords
        if cur_segs:
            stream = (cur_parts[0] if len(cur_parts) == 1
                      else jnp.concatenate(cur_parts))
            vstream = None
            if cur_vparts:
                vstream = (cur_vparts[0] if len(cur_vparts) == 1
                           else jnp.concatenate(cur_vparts))
            buckets.append((cur_segs, stream, vstream))
        cur_parts, cur_vparts, cur_segs = [], [], []
        cur_words = cur_velems = cur_coords = 0

    with stage("pack"):
        for i in reversed(sparse_ids):
            sg = items[i][1]
            lp0 = wire_layout.plan(sg, fitted=cfg.rice_fitted)
            wdt = jnp.dtype(sg.values.dtype)
            v2d_full, w2d_full, nw_full = wire_layout.pack(sg, lp0)
            overflow = overflow + jnp.sum(sg.overflow())
            for (_, r0, n) in (s for c in chunk_spans([(i, lp0.layers, lp0.d)],
                                                      cap) for s in c):
                lp = dataclasses.replace(lp0, layers=n)
                w2d = w2d_full[r0:r0 + n]
                v2d = v2d_full[r0:r0 + n]
                parts = []
                if lp.layout == "rice":
                    nw = nw_full[r0:r0 + n]
                    parts.append(nw.reshape(-1))                   # counts header
                    wire += float(n * 4)
                    # mask off the fitted-parameter header bits (identity on
                    # static-format counts) — only used words are payload
                    wire = wire + 4.0 * jnp.sum(
                        nw & compaction.RICE_HDR_USED_MASK).astype(jnp.float32)
                else:
                    wire += float(n * lp.idx_len * 4)
                if lp.idx_len:
                    if lp.layout == "coo":
                        # layer strides only: coordinates are span-block-local
                        w2d = w2d + (jnp.arange(n, dtype=jnp.int32)
                                     * lp.d)[:, None]
                    parts.append(w2d.reshape(-1))
                n_vals = n * lp.val_len
                if wdt.itemsize == 4:
                    vwords, velems0 = _words_of(n_vals, wdt), -1
                    parts.append(_word_pack(v2d))
                else:
                    vwords, velems0 = 0, cur_velems
                wire += float(n_vals) * wdt.itemsize
                if codec.has_scale:
                    parts.append(_word_pack(
                        jnp.asarray(sg.scale, jnp.float32).reshape(-1)[r0:r0 + n]))
                    wire += float(n * 4)
                n_words = sum(p.shape[0] for p in parts)
                n_bytes = n_words * 4 + (0 if vwords else n_vals * wdt.itemsize)
                if (cur_words or cur_velems) and \
                        (cur_words * 4 + cur_velems * wdt.itemsize + n_bytes
                         > cap_bytes
                         or cur_coords + lp.block > cap):
                    flush()
                    velems0 = min(velems0, 0)              # offset in new bucket
                cur_segs.append((i, lp, r0, cur_words, vwords, wdt, velems0))
                cur_parts.extend(parts)
                cur_words += n_words
                cur_coords += lp.block
                if not vwords:
                    cur_vparts.append(v2d.reshape(-1))
                    cur_velems += n_vals
        flush()

    with stage("exchange"):
        pending = [(segs, jax.lax.all_gather(stream, axis, tiled=False),
                    None if vstream is None
                    else jax.lax.all_gather(vstream, axis, tiled=False))
                   for segs, stream, vstream in buckets]

    if dense_ids:
        # tiny-leaf psum, issued after the sparse buckets so the sparse
        # collectives lead the schedule; f32 like _bucketed_sync
        with stage("pack"):
            flat = jnp.concatenate([items[e][1].reshape(-1)
                                    .astype(jnp.float32) for e in dense_ids])
        with stage("exchange"):
            synced = jax.lax.pmean(flat, axis)
        off = 0
        with stage("apply"):
            for e in dense_ids:
                for i, n in items[e][2]:
                    leaf = leaves[i]
                    out[i] = (synced[off:off + n].reshape(leaf.shape)
                              .astype(leaf.dtype))
                    off += n
        wire += float(flat.size * 4)

    # --- consume, same order the buckets were issued --------------------
    # One scatter-add per BUCKET, like the sync barrier's per-bucket
    # scatter: leaf blocks are disjoint, so accumulating them together
    # keeps the exact worker-major per-coordinate add order of the
    # per-leaf formulation while running one scatter instead of
    # len(segs). Wire index words stay leaf-block-local (the documented
    # format); the bucket-local block offset is applied at decode.
    pieces: dict = {}                    # leaf id -> flat row-order pieces
    for segs, gs, gv in pending:
        compaction.check_bucket_coords(sum(s[1].block for s in segs),
                                       len(segs))
        upd_parts, coord_parts = [], []
        block_off = 0
        with stage("decode"):
            # scale-free codecs: one bucket-wide cast of the companion value
            # stream (sync casts its whole value buffer once too) — per-leaf
            # casts of sub-word dtypes cost XLA CPU a pass per leaf
            gvf = (gv.astype(jnp.float32)
                   if gv is not None and not codec.has_scale else None)
            for (i, lp, r0, w0, vwords, wdt, velems0) in segs:
                pos = w0
                wcnt = wseg = None
                if lp.layout == "rice":
                    wcnt = gs[:, pos:pos + lp.layers]
                    pos += lp.layers
                if lp.idx_len:
                    wseg = gs[:, pos:pos + lp.layers * lp.idx_len]
                    pos += lp.layers * lp.idx_len
                n_vals = lp.layers * lp.val_len
                if vwords:
                    enc = _word_unpack(gs[:, pos:pos + vwords], wdt, n_vals)
                    pos += vwords
                else:       # companion stream, native dtype — plain slice
                    enc = (gvf if gvf is not None
                           else gv)[:, velems0:velems0 + n_vals]
                if codec.has_scale:
                    scales = _word_unpack(gs[:, pos:pos + lp.layers],
                                          jnp.float32, lp.layers)
                    # per-(worker, layer) scale broadcast over the layer's
                    # value slots — elementwise, so bitwise the same decode
                    # as sync's slot_map expansion
                    decoded = codec.decode(
                        enc.reshape(m, lp.layers, lp.val_len),
                        scales[:, :, None]).reshape(m, -1)
                else:
                    decoded = enc.astype(jnp.float32)
                upd, crd = wire_layout.unpack_gathered(lp, decoded, wseg,
                                                       block_off, wcounts=wcnt)
                if lp.layout == "coo":
                    # coo coords come straight off the wire (span-local)
                    crd = crd + jnp.int32(block_off)
                upd_parts.append(upd)
                coord_parts.append(crd)
                block_off += lp.block
        with stage("apply"):
            dense = jnp.zeros((block_off,), jnp.float32)
            dense = dense.at[
                jnp.concatenate(coord_parts, axis=1).reshape(-1)].add(
                jnp.concatenate(upd_parts, axis=1).reshape(-1), mode="drop") / m
            off = 0
            for (e, lp, r0, _, _, _, _) in segs:
                _route_span(items[e][2], r0, lp.layers, lp.d,
                            dense[off:off + lp.block], pieces)
                off += lp.block
    with stage("apply"):
        _assemble_pieces(pieces, leaves, out)

    return out, wire, overflow


def _exchange_fn(cfg: CompressionConfig):
    return _overlapped_sync if cfg.exchange == "overlap" else _bucketed_sync


def _pod_key(key: jax.Array, key_axes: tuple[str, ...],
             data_axes: tuple[str, ...]) -> jax.Array:
    """Pod-stage RNG, folded from the UNFOLDED base key so it is invariant
    over the data axes: every data worker of a pod re-sparsifies the
    identical pod-averaged tree with the identical key (and therefore
    agrees bit-for-bit on the pod's message and residual), while distinct
    pods / model shards stay independent via the non-data axes."""
    key = jax.random.fold_in(key, 7)
    for a in key_axes:
        if a not in data_axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
    return key


def sync_tree(cfg: CompressionConfig, key: jax.Array, grads: Any,
              data_axis: Axis = "data", pod_axis: str | None = None,
              stacked: Any | None = None,
              key_axes: tuple[str, ...] | None = None,
              feedback: Any | None = None,
              control: ControlState | None = None):
    """THE sync entrypoint: compress local grads per leaf and exchange them
    over the data (and pod) mesh axes, dispatching wire format, exchange
    structure, bucket chunking, and hierarchy from ``cfg`` alone.

    Returns ``(synced, new_feedback, stats)``: the synchronized (averaged)
    gradient tree, the updated error-feedback state (a ``FeedbackState``;
    None unless ``cfg.error_feedback``), and SyncStats. Must be called
    where ``data_axis`` (and ``pod_axis``) are manual shard_map axes.
    ``stacked`` marks scan-over-layers leaves (compressed per layer).

    ``key_axes`` names the mesh axes whose indices fold into ``key`` for
    per-worker RNG independence. The default (None) folds the data axes
    then the pod axis — one independent stream per worker. Pass a custom
    tuple when more axes are manual at the call site (e.g. the train
    step's shard-local sync folds the model axis too); pass ``()`` only
    for a pre-folded key AND no pod-stage re-sparsification — the
    pod stage derives its data-axis-invariant key from the unfolded base
    key, so it needs the fold to happen here.

    With ``cfg.error_feedback`` the caller MUST pass ``feedback`` — a
    ``FeedbackState`` (or a bare per-worker residual tree) — and raises
    otherwise; the flag is never a silent no-op. The worker residual is
    added to the gradients before compression and the new compression
    error comes back in ``new_feedback.residual``. With
    ``cfg.resparsify_pods`` and a pod axis the pod stage carries ITS OWN
    residual (``FeedbackState.pod_residual``, per-pod, identical across
    the pod's data workers — see ``init_feedback(num_pods=...)``): the
    intra-pod average plus the carried pod residual is re-sparsified, the
    second compression's error comes back in ``new_feedback.pod_residual``,
    and nothing is silently dropped at either stage.

    With ``cfg.adaptive`` the caller MUST additionally pass ``control`` —
    a ``ControlState`` with this worker's leaf-shaped ``last_sent``,
    params-shaped ``last_avg``, one f32 ``bound`` scalar per leaf, and the
    scalar ``step`` — and the return gains a fourth element: ``(synced,
    new_feedback, new_control, stats)``. The adaptive loop (a) transmits
    the gradient DIFFERENCE ``g - delta_beta * last_sent`` (the receiver
    closes it with ``delta_beta * last_avg``), (b) SKIPS a leaf's exchange
    when its delta energy falls under ``skip_tau`` times the tracked EMA
    bound — the skipped delta (plus the carried residual) folds exactly
    into the EF residual and the wire charges one sentinel word per
    skipped row — and
    (c) under ``cfg.rice_fitted`` ships data-fitted Golomb parameters in
    the counts header. Every decision is made identically on the dense
    and sparse wires from the same targets, so dense-vs-gather
    bit-identity is preserved on every adaptive path.
    """
    data_axes = ((data_axis,) if isinstance(data_axis, str)
                 else tuple(data_axis))
    if key_axes is None:
        key_axes = data_axes + ((pod_axis,) if pod_axis is not None else ())
    else:
        key_axes = tuple(key_axes)

    if isinstance(feedback, FeedbackState):
        residual, pod_residual = feedback.residual, feedback.pod_residual
    else:
        residual, pod_residual = feedback, None

    if cfg.error_feedback and residual is None:
        raise ValueError(
            "sync_tree: error_feedback=True requires the per-worker residual "
            "tree (pass feedback=FeedbackState(...), carried through the "
            "train step); refusing to silently drop the compression error.")
    resparsify_pod_stage = cfg.resparsify_pods and pod_axis is not None
    if resparsify_pod_stage and cfg.error_feedback and pod_residual is None:
        raise ValueError(
            "sync_tree: error_feedback=True with resparsify_pods=True and a "
            "pod axis requires the per-pod residual tree too "
            "(feedback=FeedbackState(residual=..., pod_residual=...); build "
            "one with repro.optim.optimizers.init_feedback(num_pods=...)): "
            "the pod-stage re-sparsification error must be carried, not "
            "dropped.")
    if resparsify_pod_stage and not key_axes:
        raise ValueError(
            "sync_tree: resparsify_pods with a pod axis needs key_axes (the "
            "mesh axes to fold into the per-worker key) so the pod stage can "
            "derive a data-axis-invariant key from the unfolded base key; "
            "pass key_axes instead of pre-folding the key.")
    if cfg.adaptive and control is None:
        raise ValueError(
            "sync_tree: adaptive=True requires the control state (pass "
            "control=ControlState(...), built with "
            "repro.optim.optimizers.init_control and carried through the "
            "train step); delta transmission against an untracked last-sent "
            "state would silently drop gradient mass.")
    if control is not None and not cfg.adaptive:
        raise ValueError(
            "sync_tree: control state passed but cfg.adaptive=False — the "
            "control loop would be a silent no-op. Set "
            "CompressionConfig(adaptive=True, error_feedback=True) or drop "
            "the control argument.")

    with stage("compress"):
        worker_key = _worker_key(key, key_axes)

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    stk_leaves = (jax.tree_util.tree_flatten(stacked)[0]
                  if stacked is not None else [False] * len(leaves))
    overflow = jnp.asarray(0, jnp.int32)
    new_pod_res = pod_residual        # pass-through unless the pod stage runs

    # -- adaptive pre-pass: delta transmission + skip decisions -----------
    send_grads, send_leaves = grads, leaves
    res_in_leaves = skip_flags = new_bound = None
    if cfg.adaptive:
        beta = cfg.delta_beta
        res_in_leaves = jax.tree_util.tree_flatten(residual)[0]
        sent_leaves = jax.tree_util.tree_flatten(control.last_sent)[0]
        bound_leaves = jax.tree_util.tree_flatten(control.bound)[0]
        if beta:
            send_leaves = [g - beta * s for g, s in zip(leaves, sent_leaves)]
            send_grads = jax.tree_util.tree_unflatten(treedef, send_leaves)
        # per-leaf delta energy, reduced over any extra manual axes (e.g.
        # the model axis of shard-local sync) so the skip decision and the
        # bound stay uniform across one leaf's shards
        stat_axes = tuple(a for a in key_axes
                          if a not in data_axes and a != pod_axis)
        warm = control.step > 0       # step 0 primes the bound, never skips
        do_skip = cfg.skip_tau > 0.0  # static: tau=0 compiles skip out
        skip_flags, new_bound = [], []
        for g_send, b in zip(send_leaves, bound_leaves):
            # the statistic is the DELTA energy ||g - beta*S||^2 alone — the
            # leaf's new information, LASG-style. The EF residual is delivery
            # backlog, not news: folding it in would block skipping for the
            # whole EF warmup (the residual grows monotonically until the
            # sparse wire catches up with the dense gradient).
            t32 = g_send.astype(jnp.float32).reshape(-1)
            sq = jnp.sum(t32 * t32)
            if stat_axes:
                sq = jax.lax.psum(sq, stat_axes)
            b32 = jnp.asarray(b, jnp.float32).reshape(())
            # step 0 PRIMES the bound at the first observed energy instead
            # of EMA-ing from zero (which would mute skipping for the first
            # ~1/(1-decay) steps while the EMA warms up)
            new_bound.append(jnp.where(
                warm,
                jnp.float32(cfg.bound_decay) * b32
                + jnp.float32(1.0 - cfg.bound_decay) * sq,
                sq))
            if do_skip and g_send.size >= cfg.min_leaf_size:
                skip_flags.append(jnp.logical_and(
                    warm, sq <= jnp.float32(cfg.skip_tau) * b32))
            else:   # tiny dense-passthrough leaves never skip
                skip_flags.append(jnp.zeros((), bool))

    wire_inter = 0.0
    if cfg.wire == "dense":
        with stage("compress"):
            q_tree, new_res, stats = compress_tree(cfg, worker_key,
                                                   send_grads,
                                                   residual=residual,
                                                   stacked=stacked)
        if cfg.adaptive:
            # skipped leaves contribute exact zeros to the psum — the dense
            # twin of the sparse wire's masked rows
            q_tree = jax.tree_util.tree_unflatten(treedef, [
                jnp.where(f, jnp.zeros_like(q), q)
                for q, f in zip(jax.tree_util.tree_flatten(q_tree)[0],
                                skip_flags)])
        synced, wire_intra = _sync_leaves_dense(q_tree, data_axis)
        if pod_axis is not None and not cfg.resparsify_pods:
            # hierarchical mean (equal pod sizes), so the per-stage byte
            # split stays honest: intra = data-axis stage, inter = pod stage
            synced, wire_inter = _sync_leaves_dense(synced, pod_axis)
    else:   # gather | packed (validated at CompressionConfig construction)
        with stage("compress"):
            items, new_res, _, stats = compress_tree_sparse(
                cfg, worker_key, send_grads, stacked=stacked,
                residual=residual)
        skip_savings = None
        if cfg.adaptive:
            items, skip_savings = _apply_skip(cfg, items, skip_flags)
        out_leaves, wire_intra, overflow = _exchange_fn(cfg)(items, leaves,
                                                             data_axis, cfg)
        if skip_savings is not None:
            wire_intra = wire_intra - skip_savings
        synced = jax.tree_util.tree_unflatten(treedef, out_leaves)

    if cfg.adaptive:
        # a skipped leaf's WHOLE target (delta + residual) folds into the
        # residual: Q = 0, so res = target - Q = target, the same op the
        # compress paths apply — nothing is dropped
        new_res = jax.tree_util.tree_unflatten(treedef, [
            jnp.where(f, (g + r).astype(nr.dtype), nr)
            for nr, g, r, f in zip(jax.tree_util.tree_flatten(new_res)[0],
                                   send_leaves, res_in_leaves, skip_flags)])

    # Algorithm 1 step 7 (optional re-sparsification) -> inter-pod stage.
    # With error feedback the recompression error is carried in the
    # per-pod residual (identical across the pod's data workers: the
    # input, key, and carried state all are), never dropped.
    if pod_axis is not None and (cfg.resparsify_pods or cfg.wire != "dense"):
        if cfg.wire == "dense":
            # only reachable with resparsify_pods: the plain dense pod
            # stage already ran in the intra/inter split above
            pod_key = _pod_key(key, key_axes, data_axes)
            with stage("compress"):
                if cfg.error_feedback:
                    synced, new_pod_res, _ = compress_tree(
                        cfg, pod_key, synced, stacked=stacked,
                        residual=pod_residual)
                else:
                    synced, _, _ = compress_tree(cfg, pod_key, synced,
                                                 stacked=stacked)
            synced, wire_inter = _sync_leaves_dense(synced, pod_axis)
        else:
            synced_leaves = jax.tree_util.tree_flatten(synced)[0]
            with stage("compress"):
                if cfg.resparsify_pods:
                    pod_key = _pod_key(key, key_axes, data_axes)
                    if cfg.error_feedback:
                        items2, new_pod_res, _, _ = compress_tree_sparse(
                            cfg, pod_key, synced, stacked=stacked,
                            residual=pod_residual)
                    else:
                        items2, _, _, _ = compress_tree_sparse(
                            cfg, pod_key, synced, stacked=stacked)
                else:
                    items2 = _compact_items(cfg, synced_leaves, stk_leaves)
            if not cfg.resparsify_pods:
                if cfg.error_feedback:
                    # the pod-union of the data-axis workers' coordinates
                    # routinely exceeds one message's k_cap, so the
                    # deterministic pod compaction drops real mass every
                    # step: fold it into this worker's residual (every
                    # worker of the pod carries the same drop, so the next
                    # intra-pod mean reinstates it — exactly the 1/P global
                    # weight the dense pod stage would have given it)
                    with stage("compress"):
                        drops = _compaction_drops(items2, synced_leaves)
                        new_res = jax.tree.map(
                            lambda r, d: r + d, new_res,
                            jax.tree_util.tree_unflatten(treedef, drops))
            out_leaves, wire_inter, ovf2 = _exchange_fn(cfg)(
                items2, synced_leaves, pod_axis, cfg)
            synced = jax.tree_util.tree_unflatten(treedef, out_leaves)
            overflow = overflow + ovf2

    new_control = None
    if cfg.adaptive:
        if cfg.delta_beta:
            # close the delta code: the receiver reconstructs against its
            # tracked EMA of past synced averages (every worker holds an
            # identical copy, so all workers agree bit-for-bit)
            beta = cfg.delta_beta
            synced = jax.tree.map(
                lambda a, s: (beta * a + s).astype(s.dtype),
                control.last_avg, synced)
        # what this worker's wire effectively carried, folded into the
        # last-sent EMA: S' = beta*S + Q(target) = g + r_in - r_out —
        # one formula for skipped (Q=0 -> S' = beta*S) and sent rows alike
        new_control = ControlState(
            last_sent=jax.tree_util.tree_unflatten(treedef, [
                (g + r - nr).astype(g.dtype)
                for g, r, nr in zip(leaves, res_in_leaves,
                                    jax.tree_util.tree_flatten(new_res)[0])]),
            last_avg=synced if cfg.delta_beta else control.last_avg,
            bound=jax.tree_util.tree_unflatten(treedef, new_bound),
            step=control.step + jnp.int32(1))

    new_feedback = (FeedbackState(residual=new_res, pod_residual=new_pod_res)
                    if cfg.error_feedback else None)
    out_stats = SyncStats(
        bits=stats.bits, dense_bits=stats.dense_bits,
        wire_bytes=jnp.asarray(wire_intra + wire_inter, jnp.float32),
        wire_bytes_intra=jnp.asarray(wire_intra, jnp.float32),
        wire_bytes_inter=jnp.asarray(wire_inter, jnp.float32),
        density=stats.density, var_ratio=stats.var_ratio,
        overflow=overflow.astype(jnp.float32),
        skipped=(sum(f.astype(jnp.float32) for f in skip_flags)
                 if cfg.adaptive else jnp.zeros((), jnp.float32)),
    )
    if control is not None:
        return synced, new_feedback, new_control, out_stats
    return synced, new_feedback, out_stats
