"""Value codecs: the "how many bits per value" half of a compression scheme.

The paper's coding model (section 3.3) factors a message into two orthogonal
choices — *which* coordinates travel (the selector, repro.core.schemes) and
*how many bits each kept value costs* (this module). A ``ValueCodec`` owns
the wire representation of kept values: the buffer dtype the collective
actually moves, the per-value bit cost in the coding model, and the
(en|de)code pair between full-precision values and that representation.

Codecs are elementwise given a per-message ``scale``, so encode/decode
commute with compaction: encoding the dense layout and gathering at the
kept indices equals encoding the compact buffer — which is what keeps the
dense and gather wires bit-identical under the same key.

Registered codecs:
  f32     -- passthrough at the leaf dtype; value_bits = b (the coding
             model's float width). The classic paper configuration.
  bf16    -- round kept values to bfloat16 (the old 'packed' wire transform,
             now a first-class codec usable on any wire).
  qsgd<N> -- QSGD [Alistarh et al. 2017] stochastic quantization of kept
             values to s = 2^N - 1 levels of |v| / ||v||_2; integer levels
             on the wire plus one f32 scale per message.
  ternary -- TernGrad [Wen et al. 2017] values: stochastic rounding to
             {-scale, 0, +scale} with scale = max|v|; int8 signs on the
             wire plus one f32 scale. Composed with the bernoulli selector
             this is *exactly* TernGrad (every kept value is already
             sign(g) * max|g|, so the rounding is lossless there).

``encode(vals, scale, u)`` takes pregenerated uniforms for the stochastic
codecs (the paper's section-5.3 trick keeps both wire paths bit-exact and
testable); ``u=None`` falls back to deterministic round-to-nearest, used by
the keyless pod-stage re-compaction.

Kernel-side encode contract
---------------------------
The fused pallas backend runs ``encode`` *inside* the compact-write kernel
tile (``kernels.sparsify.kernel.compact_emit_2d``), so every codec promises:

  1. ``encode``/``decode`` are elementwise given ``scale`` and the
     per-value uniform — pure jnp ops on the value lane, no reductions, no
     data-dependent shapes. Encoding a tile and scattering the kept lanes
     equals encoding the gathered compact buffer, bit for bit, provided
     the uniforms line up per compact rank.
  2. The per-message ``scale`` is a streaming reduction described by
     ``scale_kind``: "none" (no scale), "l2" (sqrt of the sum of squares),
     or "max" (max absolute value) over the transmitted values. Pass 1 of
     the two-pass kernel accumulates the raw statistic per tile;
     ``finalize_scale`` turns it into the codec's scale. Tile-order
     summation may differ from the reference's single reduction in the
     last ulp (same contract the compact-buffer encode always had).
  3. ``encode(0) == 0`` for any scale/uniform, so unselected lanes and
     capacity padding stay exactly zero on the wire.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class FloatCodec:
    """Float passthrough/rounding codec (f32 at the leaf dtype, or bf16).

    ``rounding`` separates the two roles of a float width: the ``bf16``
    codec (rounding=True) actually rounds transmitted values to bfloat16,
    while the ``f32`` codec is a pure passthrough whose ``bits`` is only
    the coding model's b — ``float_bits=16`` changes the *accounting*, it
    never silently quantizes the wire."""
    bits: int = 32
    rounding: bool = False

    @property
    def name(self) -> str:
        return "bf16" if self.rounding else "f32"

    @property
    def value_bits(self) -> float:
        return float(self.bits)

    # dense-map alternative / per-message header: none — float coding keeps
    # the selector's own header (the trailing b for lambda/norm).
    dense_map_bits = None
    header_bits = 0.0
    stochastic = False
    has_scale = False
    integer_coded = False
    scale_kind = "none"

    @property
    def rounds_values(self) -> bool:
        return self.rounding

    def wire_dtype(self, leaf_dtype) -> jnp.dtype:
        return jnp.dtype(jnp.bfloat16 if self.rounding
                         else jnp.dtype(leaf_dtype))

    def scale(self, vals: jax.Array) -> jax.Array:
        return jnp.ones((), jnp.float32)

    def encode(self, vals: jax.Array, scale: jax.Array,
               u: jax.Array | None = None) -> jax.Array:
        return vals.astype(self.wire_dtype(vals.dtype))

    def decode(self, wire_vals: jax.Array, scale: jax.Array) -> jax.Array:
        return wire_vals.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class QsgdCodec:
    """QSGD levels over the kept values: level_i ~ round(s |v_i| / ||v||_2),
    signed integer levels on the wire, decode = level * scale / s."""
    bits: int = 8

    def __post_init__(self):
        if not 1 <= self.bits <= 14:
            raise ValueError(f"qsgd bits must be in [1, 14], got {self.bits}")

    @property
    def name(self) -> str:
        return f"qsgd{self.bits}"

    @property
    def levels(self) -> float:
        return float(2 ** self.bits - 1)

    @property
    def value_bits(self) -> float:
        return float(self.bits)          # sign folds into the signed level

    @property
    def dense_map_bits(self) -> float:
        return float(self.bits)          # dense level map, one entry/coord

    header_bits = 32.0                   # the scale float
    stochastic = True
    has_scale = True
    integer_coded = True
    rounds_values = True
    scale_kind = "l2"

    def wire_dtype(self, leaf_dtype) -> jnp.dtype:
        return jnp.dtype(jnp.int8 if self.levels <= 127 else jnp.int16)

    def scale(self, vals: jax.Array) -> jax.Array:
        # l2 norm of the kept values (zeros — unselected slots — contribute
        # nothing, so dense-layout and compact-buffer calls agree).
        v = vals.astype(jnp.float32).reshape(-1)
        return jnp.sqrt(jnp.sum(v * v))

    def encode(self, vals: jax.Array, scale: jax.Array,
               u: jax.Array | None = None) -> jax.Array:
        v = vals.astype(jnp.float32)
        s = self.levels
        scaled = jnp.where(scale > 0,
                           jnp.abs(v) / jnp.where(scale > 0, scale, 1.0),
                           0.0) * s
        lo = jnp.floor(scaled)
        frac = scaled - lo
        up = (frac >= 0.5) if u is None else (u < frac)
        level = lo + up.astype(jnp.float32)
        return (jnp.sign(v) * level).astype(self.wire_dtype(vals.dtype))

    def decode(self, wire_vals: jax.Array, scale: jax.Array) -> jax.Array:
        # multiply by the reciprocal, never divide by the level count: XLA
        # rewrites a division by a constant into exactly this product under
        # jit but not in eager dispatch, so a division would decode to
        # different last bits in and out of jit
        return (wire_vals.astype(jnp.float32)
                * (jnp.asarray(scale, jnp.float32)
                   * jnp.float32(1.0 / self.levels)))


@dataclasses.dataclass(frozen=True)
class TernaryCodec:
    """TernGrad values: stochastic rounding of kept values to
    {-scale, 0, +scale}, scale = max|v|; int8 signs on the wire."""

    name = "ternary"
    value_bits = 1.0                     # one sign bit per kept value
    dense_map_bits = 2.0                 # the dense ternary map of section 3.3
    header_bits = 32.0                   # the scale float
    stochastic = True
    has_scale = True
    integer_coded = True
    rounds_values = True
    scale_kind = "max"

    def wire_dtype(self, leaf_dtype) -> jnp.dtype:
        return jnp.dtype(jnp.int8)

    def scale(self, vals: jax.Array) -> jax.Array:
        return jnp.max(jnp.abs(vals.astype(jnp.float32)))

    def encode(self, vals: jax.Array, scale: jax.Array,
               u: jax.Array | None = None) -> jax.Array:
        v = vals.astype(jnp.float32)
        p = jnp.where(scale > 0,
                      jnp.abs(v) / jnp.where(scale > 0, scale, 1.0), 0.0)
        keep = (p >= 0.5) if u is None else (u < p)
        return (jnp.sign(v) * keep.astype(jnp.float32)).astype(jnp.int8)

    def decode(self, wire_vals: jax.Array, scale: jax.Array) -> jax.Array:
        return wire_vals.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


def finalize_scale(codec, sum_sq: jax.Array, max_abs: jax.Array) -> jax.Array:
    """Kernel-side half of the scale contract: fold the pass-1 streaming
    statistics (sum of squares, max abs over the transmitted values) into
    the codec's per-message scale. Mirrors ``codec.scale`` on the compact
    buffer without materializing it."""
    if codec.scale_kind == "l2":
        return jnp.sqrt(jnp.asarray(sum_sq, jnp.float32))
    if codec.scale_kind == "max":
        return jnp.asarray(max_abs, jnp.float32)
    return jnp.ones((), jnp.float32)


_QSGD_RE = re.compile(r"^qsgd(\d+)$")


def get(name: str, float_bits: int = 32):
    """Codec registry lookup. ``f32`` carries the config's float_bits as
    the coding model's b (accounting only, never rounds the wire); the
    bf16 codec is the one that actually rounds values."""
    if name in ("f32", "fp32", "float32"):
        return FloatCodec(bits=float_bits, rounding=False)
    if name == "bf16":
        return FloatCodec(bits=16, rounding=True)
    if name == "ternary":
        return TernaryCodec()
    m = _QSGD_RE.match(name)
    if m:
        return QsgdCodec(bits=int(m.group(1)))
    raise ValueError(f"unknown value codec {name!r}; have "
                     "('f32', 'bf16', 'qsgd<bits>', 'ternary')")


CODEC_NAMES = ("f32", "bf16", "qsgd4", "qsgd8", "ternary")
