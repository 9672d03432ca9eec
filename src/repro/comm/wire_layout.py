"""Self-describing wire layouts for the bucketed sparse collectives.

The paper's section-3.3 hybrid code picks, per message, between an index
list and a dense ternary map — whichever is shorter. This module realizes
that choice on the actual HLO collective: every ``SparseGrad`` leaf is
stamped with a *statically chosen* layout (from ``(k_cap, d)`` and the codec
wire width — all trace-time constants), and ``repro.comm.sync`` packs /
unpacks each per-dtype bucket accordingly:

  coo    -- today's baseline: k_cap codec-encoded values + k_cap int32
            coordinates. Wins at low density (k_cap << d / INDEX_BITS).
  bitmap -- k_cap values in coordinate order + a packed d-bit occupancy map
            in int32 words (repro.comm.compaction.bitmap_pack). The paper's
            "dense map" branch realized on the wire: wins once the int32
            index list outweighs d bits, i.e. k_cap > d / 32-ish.
  dense  -- d values in coordinate order, index stream elided entirely. The
            identity/bernoulli selectors size k_cap = d, so qsgd/terngrad
            finally ride the sparse wire with zero index overhead (and it
            also wins for near-full rho-capped buffers, where d value slots
            undercut k_cap values + any index stream).
  rice   -- wire-format v3: k_cap values in coordinate order + the sorted
            index stream delta-coded with a static-parameter Golomb-Rice
            code (repro.comm.compaction.rice_encode) into packed int32
            words. The paper's entropy-coded index list realized on the
            wire: at low density it undercuts COO by ~(32 / (log2(d/k)+2))x
            and takes the low-density regime from it outright; the encoded
            length is data-dependent, so the bucket ships it with a
            TWO-PHASE exchange (repro.comm.sync): phase one all-gathers the
            per-layer used-word counts (a tiny int32 vector), phase two
            gathers the payload padded to the static worst-case capacity
            (coding.rice_wire_words) so every collective stays static-shape
            under jit, while realized bytes are accounted from the true
            encoded lengths.

The chooser is argmin over ``coding.realized_wire_bits`` — realized bytes
are minimal per bucket *by construction* (RICE enters with its worst-case
capacity cost, so realized bytes only ever undercut the chosen bound),
which the property tests in tests/test_wire_layout.py and tests/test_rice.py
pin. All four layouts are fixed-shape, so they jit, vmap (scan-over-layers
stacks), and cross shard_map boundaries.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.comm import compaction
from repro.core import coding

LAYOUTS = ("coo", "bitmap", "dense", "rice")
# tie-break by decode cost: dense (pure slice-add) < coo (scatter) < bitmap
# (rank-gather) < rice (unary scan + rank scatter + prefix sum). Static, so
# ties resolve identically on every trace.
_PREFERENCE = ("dense", "coo", "bitmap", "rice")


def value_bits_of(dtype) -> float:
    """Wire width of one value slot in bits (the realized twin of the
    coding model's b)."""
    return float(jnp.dtype(dtype).itemsize * 8)


def choose(k_cap: int, d: int, value_bits: float,
           override: str = "auto") -> str:
    """Static layout selection for one leaf (per layer): the layout whose
    realized wire bits are minimal — the paper's shorter-of-the-branches
    rule cashed out with int32 index words. ``override`` forces a specific
    layout (CompressionConfig.wire_layout / --wire-layout)."""
    if override != "auto":
        if override not in LAYOUTS:
            raise ValueError(f"unknown wire layout {override!r}; "
                             f"have {LAYOUTS + ('auto',)}")
        return override
    return min(_PREFERENCE,
               key=lambda l: coding.realized_wire_bits(l, k_cap, d,
                                                       value_bits))


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Static wire description of one leaf's segments inside a bucket —
    what makes the bucket self-describing: every stream length and offset
    is derivable at trace time from the plans alone. For the RICE layout
    ``idx_len`` is the worst-case word CAPACITY (the static payload shape);
    the realized encoded length per layer rides the phase-one counts
    vector of the two-phase exchange."""
    layout: str
    layers: int              # 1 for flat leaves
    d: int                   # coordinates per layer
    k_cap: int
    val_len: int             # value slots per layer on the wire
    idx_len: int             # int32 index words per layer on the wire
    rice_r: int = 0          # static Golomb-Rice parameter (rice only)
    fitted: bool = False     # wire-format v4: data-fitted Rice parameter
    rice_window: tuple = ()  # static candidate parameters (fitted only)

    @property
    def block(self) -> int:
        """Coordinates this leaf spans in the bucket's flat space."""
        return self.layers * self.d


def plan(sg, fitted: bool = False) -> LeafPlan:
    """The static wire plan for one SparseGrad (layout stamped by the
    backend; ``coo`` for pre-layout producers, e.g. hand-built buffers).
    ``fitted`` switches RICE leaves to wire-format v4: the Golomb-Rice
    parameter is fitted per layer per step from the realized index gaps
    over the static candidate window (``coding.rice_fit_window``) and
    shipped in the high bits of the phase-one counts word; the payload
    capacity is the max over the window so the collective shape stays
    static while realized words only ever undercut the static-parameter
    encoder's."""
    layers = sg.values.shape[0] if sg.values.ndim == 2 else 1
    layout = sg.layout
    rice_r = 0
    rice_window: tuple = ()
    use_fitted = False
    if layout == "coo":
        val_len, idx_len = sg.k_cap, sg.k_cap
    elif layout == "bitmap":
        val_len, idx_len = sg.k_cap, compaction.bitmap_words(sg.d)
    elif layout == "dense":
        val_len, idx_len = sg.d, 0
    elif layout == "rice":
        rice_r = coding.rice_parameter(sg.k_cap, sg.d)
        val_len = sg.k_cap
        if fitted:
            use_fitted = True
            rice_window = coding.rice_fit_window(sg.k_cap, sg.d)
            idx_len = compaction.rice_fit_cap_words(sg.k_cap, sg.d,
                                                    rice_window)
        else:
            idx_len = compaction.rice_cap_words(sg.k_cap, sg.d, rice_r)
    else:
        raise ValueError(f"unknown wire layout {layout!r}; have {LAYOUTS}")
    return LeafPlan(layout=layout, layers=layers, d=sg.d, k_cap=sg.k_cap,
                    val_len=val_len, idx_len=idx_len, rice_r=rice_r,
                    fitted=use_fitted, rice_window=rice_window)


def pack(sg, lp: LeafPlan) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Encode one SparseGrad's compact buffers into its wire streams:
    ``(values [layers, val_len], index words [layers, idx_len], used word
    counts [layers])``. Index words are layer-local coordinates for coo
    (the bucket offsets them) and opaque bit words for bitmap/rice. Values
    stay codec-encoded throughout. The counts are the realized encoded
    lengths of the RICE layout's variable-length streams (zeros for the
    fixed layouts, whose idx_len IS the realized length); they feed the
    two-phase exchange's phase-one vector and the true-byte accounting.
    Coordinate-sorted producers (``sg.idx_sorted``) pack bitmap and rice
    sort-free from their authoritative nnz. A leaf whose RICE stream is
    already packed (``sg.rice_words``, set by the adaptive loop's skip
    masking) ships those words as-is."""
    if lp.layout == "rice" and sg.rice_words is not None:
        if sg.values.ndim == 2:
            return sg.values, sg.rice_words, sg.rice_used
        return (sg.values[None, :], sg.rice_words[None, :],
                sg.rice_used[None])
    zero = jnp.zeros((), jnp.int32)

    def one(vals, idx, nnz):
        if lp.layout == "coo":
            return vals, idx, zero
        if lp.layout == "dense":
            # coordinate order = a scatter of the compact pair; padding
            # slots add exact zeros, live coordinates are unique, so this
            # is the dense wire array bit-for-bit (encode and scatter
            # commute for the elementwise codecs).
            return (compaction.scatter(vals, idx, lp.d),
                    jnp.zeros((0,), jnp.int32), zero)
        srt = nnz if sg.idx_sorted else None
        if lp.layout == "rice":
            if lp.fitted:
                return compaction.rice_encode_fitted(vals, idx, lp.d,
                                                     lp.rice_window, nnz=srt)
            return compaction.rice_encode(vals, idx, lp.d, lp.rice_r,
                                          nnz=srt)
        sv, w = compaction.bitmap_pack(vals, idx, lp.d, nnz=srt)
        return sv, w, zero

    if sg.values.ndim == 2:
        return jax.vmap(one)(sg.values, sg.idx, sg.nnz)
    v, w, n = one(sg.values, sg.idx, sg.nnz)
    return v[None, :], w[None, :], n[None]


def unpack_gathered(lp: LeafPlan, decoded: jax.Array, widx: jax.Array | None,
                    coord_off: int, wcounts: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Turn one leaf's gathered+decoded segment back into scatter-ready
    ``(updates [m, X], coords [m, X])`` against the bucket's flat space.

    ``decoded [m, layers*val_len]`` is the codec-decoded value segment;
    ``widx [m, layers*idx_len]`` the index-word segment (coo words arrive
    already globally offset; None for dense). ``wcounts [m, layers]`` are
    the phase-one gathered encoded lengths of a RICE leaf: padding words
    past each worker's count are zeroed before decoding, so the decode
    depends only on bits the sender actually encoded. The per-worker
    update values are exact — bitmap decoding is a pure rank-gather, dense
    an iota, rice a prefix-sum of decoded gaps whose dead tail is masked
    to a dropped coordinate by its zero value — so one bucket-wide
    scatter-add accumulates every layout in the same worker-major order,
    keeping the sparse wires bit-identical to the dense psum's sequential
    reduction.
    """
    m = decoded.shape[0]
    if lp.layout == "coo":
        return decoded, widx
    if lp.layout == "rice":
        words = widx.reshape(m, lp.layers, lp.idx_len)
        if wcounts is not None:
            # static counts carry no header bits, so the mask is identity
            # on them; fitted counts pack (r << RICE_HDR_SHIFT) | used
            used = wcounts & compaction.RICE_HDR_USED_MASK
            words = jnp.where(jnp.arange(lp.idx_len, dtype=jnp.int32)
                              < used[..., None], words, 0)
        if lp.fitted:
            sidx = compaction.rice_decode_fitted(words, lp.k_cap, lp.d,
                                                 lp.rice_window, wcounts)
        else:
            sidx = compaction.rice_decode(words, lp.k_cap, lp.d, lp.rice_r)
        coords = (sidx
                  + (jnp.arange(lp.layers, dtype=jnp.int32) * lp.d)[None, :,
                                                                    None]
                  + jnp.int32(coord_off)).reshape(m, -1)
        # dead tail / codec-zeroed slots: zero value -> dropped coordinate
        # (their decoded indices run past the live stream)
        coords = jnp.where(decoded != 0, coords,
                           jnp.int32(compaction.INT32_COORD_LIMIT))
        return decoded, coords
    iota = jnp.broadcast_to(jnp.arange(lp.block, dtype=jnp.int32)
                            + jnp.int32(coord_off), (m, lp.block))
    if lp.layout == "dense":
        return decoded, iota
    dense = compaction.bitmap_select(
        widx.reshape(m, lp.layers, lp.idx_len),
        decoded.reshape(m, lp.layers, lp.val_len), lp.d)
    return dense.reshape(m, lp.block), iota
