"""Fixed-capacity compaction of sparsified gradients for TPU collectives.

XLA collectives need static shapes, so the paper's variable-length sparse
messages become fixed-capacity (values, indices) buffers:

    k_cap = ceil(capacity_slack * rho * d)   (rounded up to a multiple of 128)

Selection into the buffer is by magnitude, so when the realized nnz exceeds
k_cap the *smallest* entries are dropped (overflow). We report the overflow
mass; with slack >= 1.25 it is measured to be ~0 for d >= 2**14 (binomial
concentration), keeping the estimator effectively unbiased.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# A bucket concatenates every leaf of a wire-dtype group into one int32
# coordinate space; beyond this many coordinates the offsets wrap negative
# and the scatter-add silently drops (mode="drop") every wrapped leaf.
INT32_COORD_LIMIT = 2**31 - 1


def check_bucket_coords(total_coords: int, n_leaves: int) -> None:
    """Guard the int32 coordinate space of one bucketed collective.

    ``total_coords`` is a static (trace-time) Python int — the sum of leaf
    sizes in one wire-dtype bucket — so this raises at trace/compile time,
    never on device.
    """
    if total_coords > INT32_COORD_LIMIT:
        raise ValueError(
            f"sparse-wire bucket would span {total_coords} coordinates "
            f"across {n_leaves} leaves, which exceeds the int32 index "
            f"limit ({INT32_COORD_LIMIT}); the concatenated offsets would "
            "wrap negative and the scatter-add would silently drop every "
            "wrapped leaf. Oversized buckets are chunked automatically "
            "into capacity-bounded collectives (the plan-level "
            "CompressionConfig.bucket_coord_cap knob, default 2^31-1, "
            "see repro.core.grouping.chunk_spans), so reaching this guard "
            "means a caller bypassed the chunker with a hand-built bucket: "
            "lower bucket_coord_cap, or shard rows wider than the cap over "
            "the model axis before compression.")


def capacity_for(d: int, rho: float, slack: float = 1.25) -> int:
    """Static message capacity for a leaf of size d at target density rho."""
    k = (int(slack * rho * d) + 127) // 128 * 128
    return min(d, max(128, k))


def compact(q: jax.Array, k_cap: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pack the nonzeros of q into (values[k_cap], idx[k_cap], nnz).

    ``nnz`` is the nonzero count of q *before* the capacity cut — the single
    authoritative count callers derive overflow from
    (``max(nnz - k_cap, 0)``). idx entries for unused slots point at slot of
    a zero value, so scatter-add of (values, idx) reconstructs q exactly
    (modulo overflow drops).
    """
    flat = q.reshape(-1)
    mag = jnp.abs(flat.astype(jnp.float32))
    vals_mag, idx = jax.lax.top_k(mag, k_cap)
    # mask padding slots; the zero literal must carry the input dtype, or
    # bf16/f16 values get silently promoted and the packed-wire byte
    # accounting (dtype.itemsize) reports f32 traffic.
    vals = jnp.where(vals_mag > 0, flat[idx], jnp.zeros((), flat.dtype))
    vals = vals.astype(flat.dtype)
    nnz = jnp.sum((mag > 0).astype(jnp.int32))
    return vals, idx.astype(jnp.int32), nnz


def scatter(vals: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """Dense reconstruction: zeros(d).at[idx].add(vals).

    add (not set) so that stacked multi-worker buffers can be scattered in one
    shot: scatter(vals.reshape(-1), idx.reshape(-1), d) sums contributions.
    """
    out = jnp.zeros((d,), vals.dtype)
    return out.at[idx.reshape(-1)].add(vals.reshape(-1), mode="drop")


# ---------------------------------------------------------------------------
# Bitmap index coding (the BITMAP wire layout, repro.comm.wire_layout):
# the compact idx stream becomes a packed d-bit occupancy map in int32 words.
# Everything here is fixed-shape bit arithmetic — it jits, vmaps (stacked
# leaves), and crosses shard_map boundaries like any other array op.
# ---------------------------------------------------------------------------

WORD_BITS = 32


def bitmap_words(d: int) -> int:
    """int32 words needed for a d-bit occupancy map."""
    return -(-d // WORD_BITS)


def coordinate_order(vals: jax.Array, idx: jax.Array, d: int,
                     nnz: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """The liveness/ordering rule shared by every coordinate-ordered wire
    codec (bitmap, rice): ``(values, idx)`` compact pair -> ``(svals,
    sidx)`` with live slots ascending by coordinate and dead slots keyed
    to the sentinel ``d`` at the tail.

    Generic path (``nnz=None``): a slot is live iff its value is nonzero
    (compaction padding and codec-zeroed levels reconstruct to zero by
    absence either way). Live coordinates are unique by construction (one
    top_k / one counting pass per leaf), so instead of one argsort over
    (key, value) pairs the keys sort alone and each value finds its slot
    by rank (a binary search against the sorted keys) — measurably
    cheaper than the pair sort on CPU XLA, and bit-identical: dead slots
    all carry value zero, so their (arbitrary) ordering within the tail
    is unobservable.

    Sorted path (``nnz`` given): for buffers whose valid prefix
    (``min(nnz, k_cap)`` slots) is already in ascending coordinate order
    — the pallas counting compaction, flagged by ``SparseGrad.idx_sorted``
    — the O(k log k) argsort is elided: values stay put and only the
    dead tail is re-keyed. Every valid-prefix slot stays live, including
    codec-zeroed levels: a zero value at a kept coordinate reconstructs
    to exactly zero.
    """
    flat = vals.reshape(-1)
    k = flat.shape[0]
    if nnz is None:
        key = jnp.where(flat != 0, idx.reshape(-1), jnp.int32(d))
        sidx = jnp.sort(key)
        pos = jnp.searchsorted(sidx, key, side="left").astype(jnp.int32)
        pos = jnp.where(key < d, pos, jnp.int32(k))  # dead slots: drop
        svals = jnp.zeros((k,), flat.dtype).at[pos].set(flat, mode="drop")
        return svals, sidx
    valid = (jnp.arange(flat.shape[0], dtype=jnp.int32)
             < jnp.minimum(nnz, flat.shape[0]))
    return flat, jnp.where(valid, idx.reshape(-1), jnp.int32(d))


def bitmap_pack(vals: jax.Array, idx: jax.Array, d: int,
                nnz: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """(values, idx) compact pair -> (coordinate-ordered values, occupancy
    words).

    Liveness/ordering is ``coordinate_order`` (shared with the RICE
    codec): live slots ascend by coordinate, dead slots (generic path:
    zero-valued; sorted path: beyond the nnz prefix) key to the sentinel
    ``d`` and carry no bit, so the receiver's rank-gather
    (``bitmap_select``) reconstructs the message exactly. The word
    scatter-add never collides bits (live coordinates are unique).
    """
    svals, sidx = coordinate_order(vals, idx, d, nnz=nnz)
    word = jnp.where(sidx < d, sidx // WORD_BITS, bitmap_words(d))  # dead: drop
    bit = jnp.uint32(1) << (sidx % WORD_BITS).astype(jnp.uint32)
    words = jnp.zeros((bitmap_words(d),), jnp.uint32).at[word].add(
        jnp.where(sidx < d, bit, jnp.uint32(0)), mode="drop")
    # int32 on the wire: the sparse buckets concatenate index streams as
    # int32, so bit 31 rides the sign bit via a bitcast (never a convert,
    # which would be UB past 2^31).
    return svals, jax.lax.bitcast_convert_type(words, jnp.int32)


def _unpack_bits(words: jax.Array) -> jax.Array:
    """int32 words [..., W] -> int32 bit array [..., W*32], LSB-first."""
    u = jax.lax.bitcast_convert_type(words, jnp.uint32)
    bits = (u[..., :, None] >> jnp.arange(WORD_BITS, dtype=jnp.uint32)) \
        & jnp.uint32(1)
    return bits.reshape(bits.shape[:-2] + (-1,)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Golomb-Rice index coding (the RICE wire layout, repro.comm.wire_layout):
# the sorted coordinate stream is delta-coded and each gap-1 is Rice-coded
# with a static per-leaf parameter r (repro.core.coding.rice_parameter).
#
# Stream layout per layer (what makes parallel fixed-shape decode possible):
#
#   [ k_cap fixed r-bit remainders | unary quotients | zero padding ]
#
# The remainder field sits at bit offset 0 with a static size (k_cap * r),
# so the decoder slices it without knowing any code length. The unary field
# holds the k_cap quotients as q one-bits followed by a 0 terminator each —
# and because NO remainder bits live there, every 0-bit in the unary region
# is a terminator: the i-th code's quotient falls out of the positions of
# the first k_cap zero bits (a rank histogram and prefix scans), with no
# sequential walk over code boundaries. Encoded length is data-dependent
# (the realized wire cost) but every buffer shape is static: the word
# capacity bounds any possible stream (rice_cap_words), and padding is
# zeros. Everything jits, vmaps (stacked leaves), and crosses shard_map
# boundaries like the bitmap ops above.
# ---------------------------------------------------------------------------

# Rice shifts stay inside int32 coordinate arithmetic.
RICE_MAX_R = 30

# Fitted-parameter header word (wire-format v4, docs/WIRE_FORMAT.md): when a
# leaf ships a DATA-FITTED Rice parameter, its phase-one counts entry becomes
# ``(r << RICE_HDR_SHIFT) | used`` — the fitted r rides the high bits of the
# word the two-phase exchange already moves, so the parameter travels for
# free. r <= RICE_MAX_R fits in 5 bits (26 + 5 = 31: the sign bit stays
# clear), and 2^26 words = 256 MB of index stream per layer bounds any
# realistic used count. Static-parameter counts have zero high bits, so
# masking with RICE_HDR_USED_MASK is the identity on them — the accounting
# and padding-zeroing paths apply it unconditionally.
RICE_HDR_SHIFT = 26
RICE_HDR_USED_MASK = (1 << RICE_HDR_SHIFT) - 1


def rice_cap_words(k_cap: int, d: int, r: int) -> int:
    """int32 words that bound ANY Rice-coded index stream of one layer:
    k_cap codes pay (r + 1) fixed bits each (remainder + terminator), and
    the unary quotient total is bounded by (d - 1) >> r — sorted unique
    coordinates in [0, d) delta-coded against -1 sum to at most d - 1
    after the per-code -1, and dead (padding) slots code a zero quotient.

    This static bound is both the payload buffer size (the collective's
    shape — encoding can never truncate) and the chooser's cost for the
    RICE branch (repro.core.coding.realized_wire_bits): RICE is only
    picked where even its worst case beats COO/BITMAP/DENSE, so realized
    bytes can only come in under the prediction, never over.
    """
    return -(-(k_cap * (r + 1) + ((max(d, 1) - 1) >> r)) // WORD_BITS)


def rice_encode(vals: jax.Array, idx: jax.Array, d: int, r: int,
                nnz: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(values, idx) compact pair -> (coordinate-ordered values, packed
    Rice code words [rice_cap_words], used word count).

    Liveness/ordering is ``coordinate_order`` (shared with
    ``bitmap_pack``, incl. its argsort-free sorted path for
    ``SparseGrad.idx_sorted`` producers). Exactly k_cap gaps are coded:
    live slots carry their sorted-coordinate delta, dead slots code gap 1
    (quotient 0) at the tail, where the receiver masks them by their zero
    value. The used word count is the realized wire cost of this message
    — what the two-phase exchange's phase-one counts vector reports —
    while the returned word buffer always has the static capacity shape,
    zero-padded past the encoded region.
    """
    svals, sidx = coordinate_order(vals, idx, d, nnz=nnz)
    words, used = _rice_pack_gaps(_rice_gaps(sidx, d), r,
                                  rice_cap_words(svals.shape[0], d, r))
    return svals, words, used


def _rice_gaps(sidx: jax.Array, d: int) -> jax.Array:
    """Coordinate-ordered index stream -> the gap-1 codes every Rice
    candidate packs: live slots carry their sorted-coordinate delta minus
    one, dead slots (sentinel ``d``) code 0."""
    live = sidx < d
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sidx[:-1]])
    return jnp.where(live, sidx - prev - 1, 0)   # gap - 1; dead slots code 0


def _rice_pack_gaps(x: jax.Array, r: int,
                    cap_words: int) -> tuple[jax.Array, jax.Array]:
    """Pack k gap-1 codes at parameter ``r`` into ``cap_words`` int32 words
    (the shared body of ``rice_encode`` and the fitted candidate sweep —
    ``cap_words`` may exceed the minimal capacity, which only widens the
    zero-padded unary region). Returns ``(words [cap_words], used)``.

    Built word by word, never bit by bit: an array of one element per bit
    with a 32-wide minor axis takes four to a hundred times its size in
    TPU memory (tiles pad the minor axis to 128 lanes)."""
    k = x.shape[0]
    q = x >> r
    # remainder field: code i's r bits at bit offset i*r, LSB-first; a
    # field spans at most two words, and fields never share a bit, so the
    # scatter-add is a bitwise or
    pos = jnp.arange(k, dtype=jnp.int32) * r
    word, off = pos >> 5, (pos & 31).astype(jnp.uint32)
    rem = (x & ((1 << r) - 1)).astype(jnp.uint32)
    words = jnp.zeros((cap_words,), jnp.uint32)
    if r > 0:
        spill = jnp.where(off + r > WORD_BITS,
                          rem >> ((WORD_BITS - off) & 31), 0)
        words = words.at[word].add(rem << off).at[word + 1].add(
            spill.astype(jnp.uint32), mode="drop")
    # unary field from bit k*r: q_i one-bits then a 0 terminator, i.e. ones
    # over [k*r, k*r + total_unary) but for the terminators, which land at
    # k*r + (inclusive cumsum q)_i + i
    start = jnp.int32(k * r)
    total_unary = jnp.sum(q) + k
    lo = jnp.clip(start - jnp.arange(cap_words, dtype=jnp.int32) * 32, 0, 32)
    hi = jnp.clip(start + total_unary
                  - jnp.arange(cap_words, dtype=jnp.int32) * 32, 0, 32)
    ones = _low_ones(hi) & ~_low_ones(lo)
    tpos = start + jnp.cumsum(q) + jnp.arange(k, dtype=jnp.int32)
    terms = jnp.zeros((cap_words,), jnp.uint32).at[tpos >> 5].add(
        jnp.uint32(1) << (tpos & 31).astype(jnp.uint32), mode="drop")
    words = words | (ones & ~terms)
    used = (jnp.int32(k * r) + total_unary + (WORD_BITS - 1)) // WORD_BITS
    return (jax.lax.bitcast_convert_type(words, jnp.int32),
            used.astype(jnp.int32))


def _low_ones(n: jax.Array) -> jax.Array:
    """uint32 words with their ``n`` low bits set, n in [0, 32]."""
    full = jnp.uint32(0xFFFFFFFF)
    return jnp.where(n >= WORD_BITS, full,
                     (jnp.uint32(1) << (n & 31).astype(jnp.uint32))
                     - jnp.uint32(1))


def rice_fit_cap_words(k_cap: int, d: int, window: tuple[int, ...]) -> int:
    """Static word capacity of a FITTED Rice stream: the max capacity over
    the candidate window (the payload must hold whichever candidate the
    data picks). Padding past the realized stream is zeros and is never
    charged — realized bytes come from the header's used count."""
    return max(rice_cap_words(k_cap, d, r) for r in window)


def rice_encode_fitted(vals: jax.Array, idx: jax.Array, d: int,
                       window: tuple[int, ...],
                       nnz: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Data-fitted twin of ``rice_encode``: encode the gap stream at every
    candidate parameter in the static ``window``
    (repro.core.coding.rice_fit_window) and ship the shortest.

    Returns ``(svals, words [rice_fit_cap_words], header)`` where
    ``header = (r << RICE_HDR_SHIFT) | used`` — the fitted parameter rides
    the counts word the two-phase exchange already moves. Ties break to
    the SMALLEST candidate r (the window is ascending and argmin takes the
    first minimum), so the choice is deterministic and an all-dead stream
    always lands on ``window[0]``. The static parameter is always in the
    window, so the fitted used count never exceeds the static one."""
    svals, sidx = coordinate_order(vals, idx, d, nnz=nnz)
    x = _rice_gaps(sidx, d)
    cap = rice_fit_cap_words(svals.shape[0], d, window)
    packed = [_rice_pack_gaps(x, r, cap) for r in window]
    useds = jnp.stack([u for _, u in packed])            # [C]
    best = jnp.argmin(useds)
    words = jnp.stack([w for w, _ in packed])[best]
    r_best = jnp.asarray(window, jnp.int32)[best]
    header = (r_best << RICE_HDR_SHIFT) | useds[best]
    return svals, words, header


def rice_decode_fitted(words: jax.Array, k_cap: int, d: int,
                       window: tuple[int, ...],
                       header: jax.Array) -> jax.Array:
    """Decode a fitted Rice stream from the shipped header: the receiver
    runs the (static-shape) decode at every window candidate and selects
    by the header's r bits — the header is decode-authoritative, nothing
    else names the parameter. A zeroed header (the skip sentinel) selects
    r = ``header >> shift`` = 0 over all-zero words, which decodes to the
    0..k_cap-1 coordinate ramp; every slot carries a zero value there, so
    the receiver's zero-value masking drops the whole message."""
    r_sel = (header >> RICE_HDR_SHIFT) & 0x1F
    out = rice_decode(words, k_cap, d, window[0])
    for r in window[1:]:
        out = jnp.where((r_sel == r)[..., None],
                        rice_decode(words, k_cap, d, r), out)
    return out


def rice_decode(words: jax.Array, k_cap: int, d: int, r: int) -> jax.Array:
    """Decoded coordinate stream of a Rice-coded message: ``words
    [..., W]`` (int32 code words, W * 32 >= k_cap * r) -> ``idx [...,
    k_cap]`` (int32, stream order = ascending coordinate order — aligned
    with the coordinate-ordered value buffer). Slots past the live count
    decode to whatever the tail's zero-quotient codes cumsum to; the
    receiver must mask them by their zero value
    (repro.comm.wire_layout.unpack_gathered does). Batch dims are
    supported; everything is fixed-shape.

    Word-level throughout, with no gather and every scatter
    one-dimensional: the batch dims are folded into a flat slot index. A
    per-bit array, or a scatter batched over [workers, layers], would
    carry a minor axis of 32 bits or of 2-3 index components that TPU
    tiling pads to 128 lanes.
    """
    batch = words.shape[:-1]
    n_words = words.shape[-1]
    rows = 1
    for b in batch:
        rows *= b
    u = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(rows, n_words)
    # the unary field, realigned to start at a word boundary; bits past the
    # end of the message read as ones, so they never count as terminators
    w0, s = divmod(k_cap * r, WORD_BITS)
    uw = u[:, w0:]
    if s:
        nxt = jnp.concatenate(
            [u[:, w0 + 1:], jnp.full((rows, 1), 0xFFFFFFFF, jnp.uint32)],
            axis=1)
        uw = (uw >> s) | (nxt << (WORD_BITS - s))
    zpos = first_zero_bits(uw, k_cap, n_words * WORD_BITS - k_cap * r)
    # code i's quotient is zpos_i - zpos_{i-1} - 1 (zpos_{-1} = -1), so the
    # quotients of codes 0..i sum to zpos_i - i, and coordinate i (the
    # gaps (q << r | rem) + 1 summed, less one) is, with int32 wrap-around:
    i = jnp.arange(k_cap, dtype=jnp.int32)
    idx = ((zpos - i) << r) + i
    if r > 0:
        idx = idx + jnp.cumsum(_rice_remainders(u, k_cap, r), axis=1)
    return idx.reshape(batch + (k_cap,))


def _rice_remainders(u: jax.Array, k_cap: int, r: int) -> jax.Array:
    """The k_cap r-bit remainder fields at bit 0 of each row of ``u
    [rows, W]`` -> ``[rows, k_cap]`` int32, read at static offsets: 32
    codes fill exactly r words, so code j of every 32-code block starts in
    word (j * r) >> 5 of its block and spills at most into the block's
    next word. Each j is a strided slice; the 32 of them interleave into
    code order by one transpose."""
    rows = u.shape[0]
    nb = -(-k_cap // WORD_BITS)
    field = u[:, :nb * r]
    if field.shape[1] < nb * r:       # the last block's codes past k_cap
        field = jnp.pad(field, ((0, 0), (0, nb * r - field.shape[1])))
    mask = jnp.uint32((1 << r) - 1)

    def column(w):                    # word w of every block
        return jax.lax.slice(field, (0, w), field.shape, (1, r))

    cols = []
    for j in range(WORD_BITS):
        w, off = divmod(j * r, WORD_BITS)
        v = column(w) >> off
        if off + r > WORD_BITS:
            v = v | (column(w + 1) << (WORD_BITS - off))
        cols.append(v & mask)
    rem = jnp.stack(cols, axis=1).transpose(0, 2, 1)   # [rows, nb, 32]
    return rem.reshape(rows, nb * WORD_BITS)[:, :k_cap].astype(jnp.int32)


def first_zero_bits(uw: jax.Array, k_cap: int, u_cap: int) -> jax.Array:
    """Bit position (LSB-first, word by word) of the (i+1)-th zero bit of
    each row of ``uw [rows, n_u]`` uint32 -> ``[rows, k_cap]`` int32,
    ascending, or ``u_cap`` where the row holds fewer zeros. Two callers:
    the Rice decoder, whose zeros in the unary region are the codes'
    terminators, and the emit ops' compaction
    (``repro.kernels.sparsify.ops``), whose zeros in a complemented keep
    mask are the kept coordinates.

    A rank histogram and prefix scans, not a search: its cost grows with
    n_u + k_cap. Word w, with ex[w] zeros before it, holds zeros ex[w] + 1
    .. ex[w] + zeros[w]. Every word is added to slot min(ex[w], k_cap) of a
    per-row table of k_cap + 1 slots (the last drops the words past zero
    k_cap); ex never falls along a row, so the flat slot indices are
    sorted. For slot s, the last word W with ex[W] <= s holds zero s + 1
    (if the row has that many), and prefix scans over the slots give W
    (the words counted, less one), its bits (each word added as its
    difference from the word before it) and ex[W] (the last slot any
    word landed in).
    """
    rows = uw.shape[0]
    zeros = WORD_BITS - jax.lax.population_count(uw).astype(jnp.int32)
    ex = _cumsum(zeros) - zeros
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    slot = (row * (k_cap + 1) + jnp.minimum(ex, k_cap)).reshape(-1)

    def ranked(x):
        t = jnp.zeros((rows * (k_cap + 1),), x.dtype).at[slot].add(
            x.reshape(-1), indices_are_sorted=True,
            mode="promise_in_bounds")
        return t.reshape(rows, k_cap + 1)[:, :k_cap]

    prev = jnp.concatenate([jnp.zeros((rows, 1), jnp.uint32), uw[:, :-1]],
                           axis=1)
    hist = ranked(jnp.ones_like(zeros))
    wi = _cumsum(hist) - 1
    word = _cumsum(ranked(uw - prev))
    s = jnp.arange(k_cap, dtype=jnp.int32)
    ex_w = _scan(jnp.where(hist > 0, s, -1), jax.lax.cummax, jnp.maximum)
    bit = _nth_set_bit(~word, s + 1 - ex_w)
    return jnp.where(s < jnp.sum(zeros, axis=1, keepdims=True),
                     wi * WORD_BITS + bit, u_cap)


SCAN_BLOCK = 1024


def _scan(x: jax.Array, scan, combine) -> jax.Array:
    """Inclusive ``scan`` (``jnp.cumsum`` or ``jax.lax.cummax``, whose
    binary op is ``combine``) along the rows of ``x [rows, n]``, in blocks
    of SCAN_BLOCK: the TPU compiler takes tens of seconds over one scan of
    a few million elements, and about one over blocks of them and a scan
    of the block totals."""
    rows, n = x.shape
    if n <= SCAN_BLOCK:
        return scan(x, axis=1)
    m = -(-n // SCAN_BLOCK) * SCAN_BLOCK
    y = scan(jnp.pad(x, ((0, 0), (0, m - n))).reshape(rows, -1, SCAN_BLOCK),
             axis=2)
    through = _scan(y[:, :, SCAN_BLOCK - 1], scan, combine)  # to block ends
    # (index with a slice, then a new axis: both in one index is a gather)
    y = jnp.concatenate(
        [y[:, :1], combine(y[:, 1:], through[:, :-1][:, :, None])], axis=1)
    return y.reshape(rows, m)[:, :n]


def _cumsum(x: jax.Array) -> jax.Array:
    return _scan(x, jnp.cumsum, jnp.add)


def _nth_set_bit(x: jax.Array, n: jax.Array) -> jax.Array:
    """Bit position of the ``n``-th (1-based) set bit of uint32 ``x``, by
    halving: keep to the low half while it holds at least n set bits."""
    pos = jnp.zeros(x.shape, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            x & jnp.uint32((1 << width) - 1)).astype(jnp.int32)
        up = n > low
        n = jnp.where(up, n - low, n)
        x = jnp.where(up, x >> width, x)
        pos = pos + jnp.where(up, width, 0)
    return pos


def bitmap_select(words: jax.Array, vals: jax.Array, d: int) -> jax.Array:
    """Dense reconstruction of a bitmap-coded message: ``words [..., W]``
    (int32 occupancy) + ``vals [..., k]`` (coordinate-ordered values) ->
    ``[..., d]``. The rank of each set bit (an inclusive cumsum) gathers its
    value; unset coordinates decode to exact zeros. Batch dims broadcast, so
    gathered [workers, ...] buffers and stacked leaves decode in one call.
    """
    mask = _unpack_bits(words)[..., :d]
    rank = jnp.cumsum(mask, axis=-1) - 1
    sel = jnp.take_along_axis(
        vals, jnp.clip(rank, 0, vals.shape[-1] - 1), axis=-1)
    return jnp.where(mask != 0, sel, jnp.zeros((), vals.dtype))
