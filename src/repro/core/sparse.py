"""Compact sparse-gradient representation and the pluggable compression
backend behind it.

``SparseGrad`` is the wire-native form of a compressed gradient leaf: a
fixed-capacity ``(values, idx)`` buffer pair plus per-leaf accounting. Since
the composable-compression refactor the ``values`` buffer holds the *codec-
encoded* wire representation (bf16 for the bf16 codec, int8/int16 levels for
ternary/qsgd) together with the codec's per-message ``scale``; consumers
decode with ``decode_values()``. It is a registered pytree, so it vmaps
(per-layer compression of scan-over-layers stacks), jits, and crosses
shard_map boundaries like any array pair. The selection of nonzeros into
the buffer happens exactly once, inside the backend — downstream consumers
(repro.comm) exchange the buffers as-is and never re-discover nonzeros from
a dense array.

Backends (``CompressionConfig.backend``):
  reference -- the scheme's dense-layout pipeline (selector sample + codec
               encode/decode in dense layout) followed by one magnitude
               ``top_k`` per leaf. Bit-identical to the dense-wire
               compress_tree path given the same PRNG key — the selection,
               the codec draws, and the codec scale are literally the same
               computation — which the dense-vs-gather equivalence tests
               rely on for every composition.
  pallas    -- the two-pass emit pipeline from repro.kernels.sparsify:
               pass 1 reduces per-tile survivor counts in one traversal,
               pass 2 ranks every survivor from the tile offsets, and one
               XLA scatter builds the compact (values, idx) buffers, which
               the codec encodes as the reference does. Sort-free: the
               valid prefix ascends by coordinate. Covers the gspar
               (greedy + closed), unisp, topk and bernoulli selectors;
               identity falls back to reference per leaf. On a TPU the
               kernels are compiled; off-TPU they run in interpreter mode.
  auto      -- pallas on TPU, reference elsewhere.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.comm import compaction
from repro.core import codecs as codecs_lib
from repro.core import coding
from repro.core.stages import stage


def _ones_scale():
    return jnp.ones((), jnp.float32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseGrad:
    """Fixed-capacity compact form of one compressed gradient leaf.

    For a stacked (scan-over-layers) leaf all array fields carry a leading
    layer axis and ``d``/``shape`` describe a single layer slice.
    """
    values: jax.Array        # [k_cap] codec-encoded wire values; padding
                             # slots hold exact zeros
    idx: jax.Array           # [k_cap] int32 coordinates; padding slots hold
                             # an index whose value slot is exactly zero
    nnz: jax.Array           # realized nonzero count before any capacity drop
    p_sum: jax.Array         # sum of sampling probabilities (E[nnz])
    bits: jax.Array          # coding-model message bits for this leaf
    var_ratio: jax.Array     # ||Q(g)||^2 / ||g||^2 (the paper's `var`)
    scale: jax.Array = dataclasses.field(default_factory=_ones_scale)
                             # codec per-message scale (ones for float codecs)
    d: int = dataclasses.field(metadata=dict(static=True), default=0)
    shape: tuple = dataclasses.field(metadata=dict(static=True), default=())
    codec: str = dataclasses.field(metadata=dict(static=True), default="f32")
    layout: str = dataclasses.field(metadata=dict(static=True), default="coo")
                             # wire layout (repro.comm.wire_layout): how the
                             # bucketed collective ships this leaf (coo /
                             # bitmap / dense / rice) — picked statically
                             # from (k_cap, d, wire width)
    idx_sorted: bool = dataclasses.field(metadata=dict(static=True),
                                         default=False)
                             # valid-prefix slots ascend by coordinate (the
                             # pallas counting compaction); lets the bitmap
                             # layout pack without an argsort
    rice_words: jax.Array | None = None
                             # pre-packed Golomb-Rice index words (set by
                             # the adaptive loop's skip masking, None
                             # elsewhere); wire_layout.pack ships them as-is
    rice_used: jax.Array | None = None
                             # used word count of the pre-packed stream

    @property
    def k_cap(self) -> int:
        return self.values.shape[-1]

    def overflow(self) -> jax.Array:
        """Coordinates dropped because nnz exceeded the buffer capacity."""
        return jnp.maximum(self.nnz - self.k_cap, 0)

    def expected_density(self) -> jax.Array:
        """E[nnz]/d from the sampling probabilities — the p-accounting twin
        of the realized ``nnz``; a persistent gap between the two flags a
        miscalibrated solver (see bench_wire's expected-vs-realized row)."""
        return jnp.sum(self.p_sum) / (self.d * max(1, self.p_sum.size))

    def decode_values(self) -> jax.Array:
        """Codec-decoded f32 values — what the receiver reconstructs."""
        codec = codecs_lib.get(self.codec)
        if self.values.ndim == 2:        # stacked: per-layer scale
            return jax.vmap(codec.decode)(self.values, self.scale)
        return codec.decode(self.values, self.scale)

    def realized_wire_bits(self) -> float:
        """Static bits this leaf's message puts on the collective under its
        stamped layout (values + index words; per-message scales and RICE
        phase-one counts are accounted by the sync layer alongside their
        own gathers). For the RICE layout this is the static worst-case
        capacity the chooser priced — the realized stream is data-dependent
        and only ever comes in at or under it (repro.comm.sync charges the
        true encoded lengths)."""
        layers = self.values.shape[0] if self.values.ndim == 2 else 1
        vb = float(jnp.dtype(self.values.dtype).itemsize * 8)
        return layers * coding.realized_wire_bits(self.layout, self.k_cap,
                                                  self.d, vb)

    def densify(self) -> jax.Array:
        """Dense reconstruction (modulo overflow drops), original shape."""
        vals = self.decode_values()
        if self.values.ndim == 2:        # stacked: per-layer scatter
            dense = jax.vmap(lambda v, i: compaction.scatter(v, i, self.d))(
                vals, self.idx)
            return dense.reshape((self.values.shape[0],) + tuple(self.shape))
        return compaction.scatter(vals, self.idx, self.d).reshape(self.shape)


class Backend(Protocol):
    """A gradient-compression backend: dense leaf in, SparseGrad out."""
    name: str
    # How the grouped tree plan (repro.core.grouping) lowers one shape
    # group's [rows, d] emit. True: vmap the whole stack — one batched
    # kernel launch, what the pallas grid wants. False: a rolled
    # ``lax.map`` over rows — still ONE dispatch per group in the trace,
    # but each row's working set stays cache-resident, which is how
    # XLA:CPU wins (a vmapped solver streams the full stack through
    # memory once per elementwise pass). Either lowering is bit-identical
    # to the other and to the retired per-leaf walk: every row computes
    # independently with a counter-based PRNG.
    batched_emit: bool

    def compress_sparse(self, cfg, key: jax.Array, g: jax.Array,
                        k_cap: int) -> SparseGrad:
        ...

    def compress_sparse_ef(self, cfg, key: jax.Array, g: jax.Array,
                           k_cap: int) -> tuple[SparseGrad, jax.Array]:
        """Error-feedback variant: ``g`` is the EF target (grad + carried
        residual); also returns the new residual ``g - densify(SparseGrad)``
        computed from the compact buffers (one scatter-subtract — the dense
        Q(g) layout is never materialized)."""
        ...


def _residual_from_buffers(g: jax.Array, sg: SparseGrad) -> jax.Array:
    """target minus the *transmitted* values, from the compact (values, idx)
    pair: a single scatter-subtract into the target. Padding slots carry
    exact zeros, so they are no-ops; elementwise it equals
    ``g - sg.densify()`` bit-for-bit — and hence the dense-wire residual
    ``target - Q(target)`` whenever nothing overflows the capacity (on
    overflow this form re-carries the dropped survivors' error rather than
    losing it, as the Pallas emit does). The subtracted values
    are codec-*decoded* — what the wire actually delivers — so quantization
    error of kept values (bf16 rounding, qsgd/ternary levels) is absorbed
    into the residual instead of silently dropped.

    The scatter form is also what keeps the residual bit-identical to the
    dense wire's under jit: a scatter's add never fma-contracts with the
    decode multiply that produced the update values, so the dense path
    computes its residual with the same identity-indexed scatter (see
    repro.core.api.compress_tree)."""
    flat = g.reshape(-1)
    vals = sg.decode_values().reshape(-1)
    res = flat.at[sg.idx.reshape(-1)].add(-vals.astype(flat.dtype),
                                          mode="drop")
    return res.reshape(g.shape)


def _choose_layout(cfg, codec, leaf_dtype, k_cap: int, d: int) -> str:
    """Static wire-layout stamp for one leaf (per layer): min realized
    bytes over coo/bitmap/dense/rice, or the config's forced override."""
    # lazy import: repro.comm.wire_layout pulls repro.core.coding — at
    # module level this could cycle depending on which package loads first.
    from repro.comm import wire_layout
    return wire_layout.choose(
        k_cap, d, wire_layout.value_bits_of(codec.wire_dtype(leaf_dtype)),
        cfg.wire_layout)


class ReferenceBackend:
    """The scheme's dense-layout pipeline + a single magnitude top_k per
    leaf. Shares the dense wire's computation, hence bit-identical to it."""
    name = "reference"
    batched_emit = False     # rolled per-row emit: cache-resident on CPU

    def compress_sparse(self, cfg, key, g, k_cap) -> SparseGrad:
        scheme = cfg.scheme()
        codec = scheme.codec
        if scheme.selector.name == "topk" \
                and not (codec.rounds_values or codec.integer_coded):
            # deterministic top-k with a passthrough codec needs no dense Q
            # at all: one top_k serves as both the selection and the
            # compaction.
            return self._topk_fast(cfg, scheme, g, k_cap)
        q, p, wire, scale = scheme.apply_dense(key, g)
        with stage("compact"):
            vals, idx, nnz = compaction.compact(q, k_cap)
            # wire values at the selected coordinates: encode and selection
            # commute (the codec is elementwise given the scale), and
            # padding slots point at zero-magnitude coords whose encoded
            # level is 0.
            wire_vals = wire.reshape(-1)[idx]
        bits = scheme.message_bits(q, p, g.size)
        from repro.core._compressors import finish_compressed
        cg = finish_compressed(g, q, p, bits)
        return SparseGrad(values=wire_vals, idx=idx, nnz=nnz,
                          p_sum=jnp.sum(p), bits=cg.bits,
                          var_ratio=cg.var_ratio, scale=scale, d=g.size,
                          shape=tuple(g.shape), codec=codec.name,
                          layout=_choose_layout(cfg, codec, g.dtype, k_cap,
                                                g.size))

    def _topk_fast(self, cfg, scheme, g, k_cap) -> SparseGrad:
        codec = scheme.codec
        flat = g.reshape(-1)
        d = flat.shape[0]
        k_target = scheme.selector.k_target(d)
        k = min(k_cap, k_target)
        mag = jnp.abs(flat.astype(jnp.float32))
        vals_mag, idx = jax.lax.top_k(mag, k_cap)
        keep = jnp.arange(k_cap) < k
        vals = jnp.where(keep & (vals_mag > 0), flat[idx],
                         jnp.zeros((), flat.dtype))
        q32 = vals.astype(jnp.float32)
        den = jnp.sum(mag * mag)
        var = jnp.where(den > 0, jnp.sum(q32 * q32)
                        / jnp.where(den > 0, den, 1.0), 0.0)
        logd = jnp.log2(jnp.asarray(float(d)))
        vb = codec.value_bits
        bits = float(k_target) * (vb + logd) + vb
        # nnz is the scheme's intended selection (bounded by the actual
        # nonzero supply), pre-capacity — so overflow() reports the
        # k_cap < k_target drop instead of silently hiding it.
        nnz = jnp.minimum(jnp.sum((mag > 0).astype(jnp.int32)),
                          jnp.int32(k_target))
        return SparseGrad(values=vals.astype(codec.wire_dtype(flat.dtype)),
                          idx=idx.astype(jnp.int32), nnz=nnz,
                          p_sum=jnp.asarray(float(k_target), jnp.float32),
                          bits=jnp.asarray(bits, jnp.float32),
                          var_ratio=var, d=d, shape=tuple(g.shape),
                          codec=codec.name,
                          layout=_choose_layout(cfg, codec, flat.dtype,
                                                k_cap, d))

    def compress_sparse_ef(self, cfg, key, g, k_cap):
        sg = self.compress_sparse(cfg, key, g, k_cap)
        return sg, _residual_from_buffers(g, sg)


class PallasBackend:
    """Two-pass kernel path (repro.kernels.sparsify): pass 1 reduces
    per-tile survivor counts and the accounting sums, pass 2 ranks the
    survivors (plus the in-pass EF residual for float codecs), and one XLA
    scatter builds the compact ``(values, idx)`` wire buffers, which the
    codec encodes as on the reference backend.

    Fused selectors: gspar (greedy *and* closed-form lambda), unisp, topk,
    and bernoulli (TernGrad's selection). The identity selector has no
    sparse structure to exploit and delegates to the reference backend."""
    name = "pallas"
    batched_emit = True      # vmap extends the kernel grid: one launch/group

    FUSED_SELECTORS = ("gspar", "unisp", "topk", "bernoulli")

    def __init__(self, interpret: bool = False):
        self.interpret = interpret
        self._fallback = ReferenceBackend()

    def _fused_scheme(self, cfg):
        scheme = cfg.scheme()
        return scheme if scheme.selector.name in self.FUSED_SELECTORS \
            else None

    def compress_sparse(self, cfg, key, g, k_cap) -> SparseGrad:
        scheme = self._fused_scheme(cfg)
        if scheme is None:
            return self._fallback.compress_sparse(cfg, key, g, k_cap)
        er, layout, s = self._emit(cfg, scheme, key, g, k_cap, ef=False)
        return self._finish(scheme, g, er, layout, s)

    def compress_sparse_ef(self, cfg, key, g, k_cap):
        scheme = self._fused_scheme(cfg)
        if scheme is None:
            return self._fallback.compress_sparse_ef(cfg, key, g, k_cap)
        codec = scheme.codec
        if codec.integer_coded:
            # integer codecs: the residual must subtract the DECODED wire
            # values (level * scale / s), a multiply that happens after the
            # kernel — so take the no-EF buffers and do one scatter-subtract
            # into the target, bit-identical to the reference backend,
            # rather than folding two roundings that don't cancel.
            er, layout, s = self._emit(cfg, scheme, key, g, k_cap, ef=False)
            sg = self._finish(scheme, g, er, layout, s)
            return sg, _residual_from_buffers(g, sg)
        # float codecs: the kernel emits the residual g - Q(g) in the same
        # pass (one extra HBM write, no extra read); it subtracts the value
        # rounded to the wire dtype, so bf16 rounding of kept values is
        # already charged to the residual.
        er, layout, s = self._emit(cfg, scheme, key, g, k_cap, ef=True)
        sg = self._finish(scheme, g, er, layout, s)
        return sg, er.residual.reshape(g.shape)

    def _emit(self, cfg, scheme, key, g, k_cap, ef: bool):
        """Run the two-pass emit kernel for one leaf. Returns the kernel's
        ``EmitResult``, the statically chosen wire layout, and the
        selector's accounting scalar (lambda for gspar, max|g| for
        bernoulli, None otherwise)."""
        from repro.kernels.sparsify import ops
        sel, codec = scheme.selector, scheme.codec
        flat = g.reshape(-1)
        d = flat.shape[0]
        layout = _choose_layout(cfg, codec, g.dtype, k_cap, d)
        k_sel, k_cod = scheme.split_key(key)
        # codec uniforms at compact rank (k_cap draws)
        u_cod = (jax.random.uniform(k_cod, (k_cap,), jnp.float32)
                 if codec.stochastic else None)
        kw = dict(k_cap=k_cap, codec=codec, ef=ef, interpret=self.interpret)
        if sel.name == "topk":
            er = ops.topk_emit(flat, u_cod, k_target=sel.k_target(d), **kw)
            return er, layout, None
        u = jax.random.uniform(k_sel, g.shape, jnp.float32).reshape(-1)
        if sel.name == "gspar":
            if sel.algo == "greedy":
                er, lam = ops.gspar_emit(flat, u, u_cod, rho=sel.rho,
                                         num_iters=sel.num_iters, **kw)
            else:
                er, lam = ops.closed_emit(flat, u, u_cod, eps=sel.eps, **kw)
            return er, layout, lam
        if sel.name == "unisp":
            return ops.unisp_emit(flat, u, u_cod, rho=sel.rho, **kw), \
                layout, None
        er, mx = ops.bern_emit(flat, u, u_cod, **kw)
        return er, layout, mx

    def _finish(self, scheme, g, er, layout, s) -> SparseGrad:
        """O(k_cap) accounting from the kernel's reductions + compact
        buffers: the selector's coding-model bits need p only at the kept
        coordinates (one gather), the variance numerator is a sum over the
        k_cap decoded values, and the denominator came out of pass 1."""
        sel, codec = scheme.selector, scheme.codec
        flat = g.reshape(-1)
        d = flat.shape[0]
        v32 = codec.decode(er.values, er.scale) if codec.integer_coded \
            else er.values.astype(jnp.float32)
        den = er.den
        var = jnp.where(den > 0,
                        jnp.sum(v32 * v32) / jnp.where(den > 0, den, 1.0),
                        0.0)
        vb = codec.value_bits
        logd = jnp.log2(jnp.asarray(float(d)))
        nnz = er.nnz
        p_sum = er.p_sum
        if codec.integer_coded:
            bits = coding.quantized_coding_bits(v32, d, vb,
                                                codec.dense_map_bits,
                                                codec.header_bits)
        elif sel.name == "topk":
            # deterministic k_target message — matches the reference
            # backend's _topk_fast accounting
            p_sum = jnp.asarray(float(sel.k_target(d)), jnp.float32)
            bits = jnp.asarray(float(sel.k_target(d)) * (vb + logd) + vb,
                               jnp.float32)
        elif sel.name == "unisp":
            bits = nnz.astype(jnp.float32) * (vb + logd) + vb
        else:
            # gspar / bernoulli: sure-vs-sampled split of the kept coords
            # (coding.realized_coding_bits on the compact buffer)
            a_idx = jnp.abs(flat[er.idx].astype(jnp.float32))
            if sel.name == "gspar":
                p_idx = jnp.minimum(s * a_idx, 1.0)
            else:
                p_idx = jnp.where(s > 0,
                                  a_idx / jnp.where(s > 0, s, 1.0), 0.0)
            valid = v32 != 0
            sure = p_idx >= 1.0
            n_a = jnp.sum((valid & sure).astype(jnp.float32))
            n_b = jnp.sum((valid & ~sure).astype(jnp.float32))
            bits = n_a * (vb + logd) + jnp.minimum(2.0 * d, n_b * logd) + vb
        return SparseGrad(values=er.values, idx=er.idx, nnz=nnz,
                          p_sum=p_sum, bits=bits, var_ratio=var,
                          scale=er.scale, d=d, shape=tuple(g.shape),
                          codec=codec.name, layout=layout,
                          idx_sorted=True)  # rank order is coordinate
                                            # order: the valid prefix
                                            # ascends


def resolve_backend(name: str) -> Backend:
    """Backend registry with automatic platform fallback.

    ``auto`` picks pallas on TPU and reference elsewhere. ``pallas`` runs
    compiled kernels on a TPU and never interprets them there; off-TPU it
    runs the kernels in interpreter mode so the fused path stays testable
    on CPU.
    """
    on_tpu = jax.default_backend() == "tpu"
    if name == "auto":
        name = "pallas" if on_tpu else "reference"
    if name == "reference":
        return ReferenceBackend()
    if name == "pallas":
        return PallasBackend(interpret=not on_tpu)
    raise ValueError(f"unknown backend {name!r}; "
                     "have ('auto', 'reference', 'pallas')")
