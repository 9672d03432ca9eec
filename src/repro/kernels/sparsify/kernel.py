"""Pallas TPU kernels for the paper's hot loop.

Kernel families:

  sparsify     -- fused threshold + Bernoulli sample + amplify (Q(g) given
               the greedy lambda). One read of g from HBM, one write of Q;
               the VPU analogue of the paper's SIMD note (section 3.2).
               Uniforms come either from an input buffer (the paper's
               pregenerated-randoms trick, bit-exact testable) or from the
               on-core PRNG (pltpu.prng_random_bits; production path, no
               HBM traffic for randomness).
  stats        -- single-pass block reductions: ``stats_2d`` produces
               (sum|g|, sum g^2, max|g|); ``stats_l1max_2d`` only the
               (sum|g|, max|g|) pair the greedy lambda actually consumes,
               skipping one VMEM reduction on the sparse path.
  two-pass compaction -- ``select_stats_2d`` (pass 1) runs the selector per
               tile and reduces per-tile survivor counts, p-accounting and
               the variance denominator in one traversal;
               ``compact_emit_2d`` (pass 2) re-derives the kept mask and
               gives every survivor its global compact rank (the tile's
               offset plus an in-tile prefix count on the MXU). A rank
               select over the packed keep mask and one k_cap gather of
               the values then build the compact ``(values, idx)`` wire
               buffers in XLA (``ops._two_pass``): Mosaic has no lowering
               for cumsum, vector scatter or 1-D gather, so nothing of
               size k_cap lives in the kernels.

Block layout: inputs are reshaped to [R, C] with C a multiple of 128 and
R a multiple of 8; tiles of (BLOCK_R, BLOCK_C) f32 live in VMEM
(3 x 128 x 512 x 4 B = 768 KB working set, well under the ~16 MB/core VMEM).
The two-pass kernels additionally REQUIRE C == BLOCK_C (which the ops-layer
``_pad_2d`` always produces): the grid then walks row-blocks of contiguous
flat coordinates, and row-major order inside a tile is ascending coordinate
order, so rank order is coordinate order — the ``SparseGrad.idx_sorted``
contract falls out of the layout instead of needing a sort. Tiles are never
flattened: every in-kernel array stays 2-D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 128
BLOCK_C = 512


def _sparsify_body(g_ref, u_ref, lam_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    lam = lam_ref[0, 0]
    p = jnp.minimum(lam * jnp.abs(g), 1.0)
    z = u_ref[...] < p
    safe_p = jnp.where(p > 0, p, 1.0)
    out_ref[...] = jnp.where(z, g / safe_p, 0.0).astype(out_ref.dtype)


def _sparsify_ef_body(g_ref, u_ref, lam_ref, out_ref, res_ref):
    # error-feedback variant: emit Q(g) and the residual g - Q(g) in the
    # SAME pass — one read of g, two writes, no second traversal for the
    # residual update.
    g = g_ref[...].astype(jnp.float32)
    lam = lam_ref[0, 0]
    p = jnp.minimum(lam * jnp.abs(g), 1.0)
    z = u_ref[...] < p
    safe_p = jnp.where(p > 0, p, 1.0)
    q = jnp.where(z, g / safe_p, 0.0).astype(out_ref.dtype)
    out_ref[...] = q
    # subtract the value the wire actually carries (post dtype rounding),
    # so the residual accounts for quantization of the kept values too
    res_ref[...] = (g - q.astype(jnp.float32)).astype(res_ref.dtype)


def _sparsify_prng_body(g_ref, lam_ref, seed_ref, out_ref):
    # independent stream per tile: fold the tile coordinates into the seed
    i, j = pl.program_id(0), pl.program_id(1)
    pltpu.prng_seed(seed_ref[0, 0] + i * pl.num_programs(1) + j)
    bits = pltpu.prng_random_bits(g_ref.shape)
    # the bits come as int32: an arithmetic shift would make half the draws
    # negative, and a negative draw keeps every coordinate with p > 0
    u = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) \
        * (1.0 / (1 << 24))                                    # [0, 1)
    g = g_ref[...].astype(jnp.float32)
    lam = lam_ref[0, 0]
    p = jnp.minimum(lam * jnp.abs(g), 1.0)
    z = u < p
    safe_p = jnp.where(p > 0, p, 1.0)
    out_ref[...] = jnp.where(z, g / safe_p, 0.0).astype(out_ref.dtype)


def sparsify_2d(g: jax.Array, u: jax.Array, lam: jax.Array,
                interpret: bool = False, out_dtype=None) -> jax.Array:
    """g, u: [R, C] with R % BLOCK_R == 0, C % BLOCK_C == 0. lam: scalar.

    ``out_dtype`` is the wire dtype of the emitted Q (defaults to g's): a
    float value codec (e.g. bf16) quantizes the kept values inside this
    same pass — the astype happens in VMEM on the way out, so the wire
    representation costs no extra HBM traversal."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _sparsify_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype or g.dtype),
        interpret=interpret,
        name="gspar_sparsify",
    )(g, u, lam2)


def sparsify_ef_2d(g: jax.Array, u: jax.Array, lam: jax.Array,
                   interpret: bool = False,
                   out_dtype=None) -> tuple[jax.Array, jax.Array]:
    """Fused Q(g) + residual: returns (Q, g - Q), Q in ``out_dtype`` (the
    wire dtype, default g's) and the residual in g's dtype. The
    error-feedback twin of ``sparsify_2d`` — the residual subtraction
    happens in the same VMEM tile as the sample, so the EF update costs one
    extra HBM write instead of a separate read-subtract-write pass. The
    body subtracts Q *after* the out-dtype rounding, so a quantizing wire
    dtype (bf16 codec) charges its rounding of kept values to the residual
    inside the same pass."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _sparsify_ef_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, c), out_dtype or g.dtype),
                   jax.ShapeDtypeStruct((r, c), g.dtype)],
        interpret=interpret,
        name="gspar_sparsify_ef",
    )(g, u, lam2)


def sparsify_prng_2d(g: jax.Array, lam: jax.Array, seed: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Production variant: uniforms from the on-core PRNG (no u input)."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    seed2 = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    return pl.pallas_call(
        _sparsify_prng_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), g.dtype),
        interpret=interpret,
        name="gspar_sparsify_prng",
    )(g, lam2, seed2)


def _tail_stats_body(g_ref, t_ref, n_ref, l1_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        n_ref[0, 0] = 0.0
        l1_ref[0, 0] = 0.0

    a = jnp.abs(g_ref[...].astype(jnp.float32))
    below = a < t_ref[0, 0]
    n_ref[0, 0] += jnp.sum(below.astype(jnp.float32))
    l1_ref[0, 0] += jnp.sum(jnp.where(below, a, 0.0))


def tail_stats_2d(g: jax.Array, thresh: jax.Array, interpret: bool = False):
    """Single pass: (count, sum|g|) over the sub-threshold ("active",
    non-saturated) coordinates |g| < thresh. Feeds Algorithm 3's
    saturation-aware scalar rescale without a second full-vector pass in
    XLA-land."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    t2 = jnp.asarray(thresh, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _tail_stats_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 2,
        interpret=interpret,
        name="gspar_tail_stats",
    )(g, t2)
    return out[0][0, 0], out[1][0, 0]


def _stats_body(g_ref, l1_ref, l2_ref, mx_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        l1_ref[0, 0] = 0.0
        l2_ref[0, 0] = 0.0
        mx_ref[0, 0] = 0.0

    a = jnp.abs(g_ref[...].astype(jnp.float32))
    l1_ref[0, 0] += jnp.sum(a)
    l2_ref[0, 0] += jnp.sum(a * a)
    mx_ref[0, 0] = jnp.maximum(mx_ref[0, 0], jnp.max(a))


def stats_2d(g: jax.Array, interpret: bool = False):
    """Single pass over g: (sum|g|, sum g^2, max|g|) as (1,1) f32 outputs."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    out = pl.pallas_call(
        _stats_body,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 3,
        interpret=interpret,
        name="gspar_stats",
    )(g)
    return out[0][0, 0], out[1][0, 0], out[2][0, 0]


def _stats_l1max_body(g_ref, l1_ref, mx_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        l1_ref[0, 0] = 0.0
        mx_ref[0, 0] = 0.0

    a = jnp.abs(g_ref[...].astype(jnp.float32))
    l1_ref[0, 0] += jnp.sum(a)
    mx_ref[0, 0] = jnp.maximum(mx_ref[0, 0], jnp.max(a))


def stats_l1max_2d(g: jax.Array, interpret: bool = False):
    """Single pass over g: (sum|g|, max|g|) — the pair the greedy lambda
    actually consumes. The sparse path uses this instead of ``stats_2d`` so
    the unused l2 accumulator costs no VMEM reduction."""
    r, c = g.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    out = pl.pallas_call(
        _stats_l1max_body,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 2,
        interpret=interpret,
        name="gspar_stats_l1max",
    )(g)
    return out[0][0, 0], out[1][0, 0]


# ---------------------------------------------------------------------------
# Two-pass compaction: pass 1 counts survivors per tile, pass 2 ranks them.
# ---------------------------------------------------------------------------

def prefix_operands() -> tuple[jax.Array, jax.Array]:
    """The 0/1 matrices ``_tile_prefix`` multiplies by, in bf16 (0 and 1 are
    exact there): ``up[k, j] = k <= j`` over lanes, ``lo[r, k] = k < r`` over
    rows. Built outside the kernels and held in VMEM by a constant index
    map, so each call loads them once."""
    c = jnp.arange(BLOCK_C)
    r = jnp.arange(BLOCK_R)
    return ((c[:, None] <= c[None, :]).astype(jnp.bfloat16),
            (r[None, :] < r[:, None]).astype(jnp.bfloat16))


def _tile_prefix(x, up, lo):
    """Inclusive prefix count of a boolean tile in row-major order, which is
    ascending flat coordinate order under the C == BLOCK_C layout. Mosaic
    has no cumsum lowering, so the prefix is two MXU matmuls with the
    triangular 0/1 matrices: ``x @ up`` counts each row up to lane j, and
    the lane sum of ``lo @ x`` counts the rows above. Every partial sum is
    an integer <= BLOCK_R * BLOCK_C = 2^16, exact in f32."""
    xb = x.astype(jnp.bfloat16)
    within = jnp.dot(xb, up, preferred_element_type=jnp.float32)
    above = jnp.sum(jnp.dot(lo, xb, preferred_element_type=jnp.float32),
                    axis=1, keepdims=True)
    return (within + above).astype(jnp.int32)


def _tile_select(pkind: str, g, a, u, s1, s2, tie_base, up, lo):
    """Selector applied to one 2-D tile.

    Returns (p, z, v, ties) with p the keep probability, z the kept mask, v
    the transmitted full-precision value, and ties the tile's count of
    at-threshold coordinates (topk only; 0 otherwise). The arithmetic
    replicates the reference selectors bit-for-bit:

      lam  -- gspar (greedy or closed-form): p = min(s1 * |g|, 1)
      rho  -- unisp: p = s1 on the support, 0 off it
      bern -- bernoulli/TernGrad: p = |g| / s2 (s2 = max|g|)
      topk -- deterministic: keep |g| > s1, plus the first s2 coordinates
              with |g| == s1 (XLA top_k breaks ties by lowest index, so the
              in-coordinate-order tie budget reproduces its selection);
              ``tie_base`` counts the ties in earlier tiles
    """
    if pkind == "topk":
        t = s1
        tie = (a == t) & (t > 0)
        ti = tie.astype(jnp.int32)
        tie_rank = tie_base + _tile_prefix(tie, up, lo) - ti     # exclusive
        z = (a > t) | (tie & (tie_rank < s2.astype(jnp.int32)))
        p = z.astype(jnp.float32)
        v = jnp.where(z, g, 0.0)
        return p, z, v, jnp.sum(ti)
    if pkind == "lam":
        p = jnp.minimum(s1 * a, 1.0)
    elif pkind == "rho":
        p = jnp.where(a > 0, s1, 0.0)
    elif pkind == "bern":
        p = jnp.where(s2 > 0, a / jnp.where(s2 > 0, s2, 1.0), 0.0)
    else:  # pragma: no cover - the ops layer passes only the four kinds
        raise ValueError(f"unknown select kind {pkind!r}")
    z = u < p
    safe_p = jnp.where(p > 0, p, 1.0)
    v = jnp.where(z, g / safe_p, 0.0)
    return p, z, v, jnp.zeros((), jnp.int32)


def _select_stats_body(g_ref, u_ref, s1_ref, s2_ref, up_ref, lo_ref,
                       tiles_ref, psum_ref, den_ref, tie_ref, *, pkind: str):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        psum_ref[0, 0] = 0.0
        den_ref[0, 0] = 0.0
        tie_ref[0, 0] = 0

    g = g_ref[...].astype(jnp.float32)
    a = jnp.abs(g)
    p, z, _, ties = _tile_select(pkind, g, a, u_ref[...], s1_ref[0, 0],
                                 s2_ref[0, 0], tie_ref[0, 0], up_ref[...],
                                 lo_ref[...])
    tiles_ref[0, i] = jnp.sum(z.astype(jnp.int32))
    tiles_ref[1, i] = ties
    psum_ref[0, 0] += jnp.sum(p)
    den_ref[0, 0] += jnp.sum(a * a)
    tie_ref[0, 0] += ties


def _smem(block=(1, 1)):
    return pl.BlockSpec(block, lambda i: (0, 0), memory_space=pltpu.SMEM)


def select_stats_2d(g: jax.Array, u: jax.Array, s1: jax.Array, s2: jax.Array,
                    up: jax.Array, lo: jax.Array, pkind: str,
                    interpret: bool = False):
    """Pass 1 of the two-pass compaction: run the selector per tile and
    reduce, in one traversal of g, what pass 2 and the accounting need —
    per-tile survivor and at-threshold tie counts (``tiles[0, t]`` and
    ``tiles[1, t]``, an SMEM output indexed by the grid), the sum of keep
    probabilities and sum g^2 (the variance denominator).

    Returns (tiles [2, T] int32, p_sum, den)."""
    r, c = g.shape
    assert c == BLOCK_C, "two-pass kernels require the _pad_2d layout"
    grid = (r // BLOCK_R,)
    s1_2 = jnp.asarray(s1, jnp.float32).reshape(1, 1)
    s2_2 = jnp.asarray(s2, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0))
    tiles, psum, den, _tie = pl.pallas_call(
        functools.partial(_select_stats_body, pkind=pkind),
        grid=grid,
        in_specs=[tile, tile, _smem(), _smem(),
                  pl.BlockSpec((BLOCK_C, BLOCK_C), lambda i: (0, 0)),
                  pl.BlockSpec((BLOCK_R, BLOCK_R), lambda i: (0, 0))],
        out_specs=[_smem((2, grid[0])), _smem(), _smem(), _smem()],
        out_shape=[jax.ShapeDtypeStruct((2, grid[0]), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
        name=f"select_stats_{pkind}",
    )(g, u, s1_2, s2_2, up, lo)
    return tiles, psum[0, 0], den[0, 0]


# slot of a coordinate that is not kept: past any capacity, and the mark
# the compact select's keep mask reads (slot != DROP_SLOT)
DROP_SLOT = 2**31 - 1


def _compact_emit_body(g_ref, u_ref, s1_ref, s2_ref, up_ref, lo_ref, off_ref,
                       slot_ref, val_ref, *res_ref, pkind: str, wire_dtype,
                       k_cap: int):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)
    _, z, v, _ = _tile_select(pkind, g, jnp.abs(g), u_ref[...], s1_ref[0, 0],
                              s2_ref[0, 0], off_ref[1, i], up_ref[...],
                              lo_ref[...])
    rank = off_ref[0, i] + _tile_prefix(z, up_ref[...], lo_ref[...]) \
        - z.astype(jnp.int32)
    slot_ref[...] = jnp.where(z, rank, DROP_SLOT)
    val_ref[...] = v
    if res_ref:
        # subtract what the wire carries (v rounded to the wire dtype); a
        # survivor ranked past k_cap is not sent and stays whole
        sent = jnp.where(z & (rank >= k_cap), 0.0,
                         v.astype(wire_dtype).astype(jnp.float32))
        res_ref[0][...] = (g - sent).astype(res_ref[0].dtype)


def compact_emit_2d(g: jax.Array, u: jax.Array, s1: jax.Array, s2: jax.Array,
                    offsets: jax.Array, up: jax.Array, lo: jax.Array, *,
                    pkind: str, k_cap: int, wire_dtype=None, ef: bool = False,
                    interpret: bool = False):
    """Pass 2 of the two-pass compaction: re-derive the kept mask per tile
    and give every survivor its global compact rank.

    ``offsets[0, t]`` / ``offsets[1, t]`` are the exclusive prefix sums of
    pass 1's per-tile survivor / tie counts, formed outside the kernel, so
    the tiles are independent. Emits ``(slot [r, c] int32, v [r, c] f32,
    residual)``: ``slot`` is the compact rank of each survivor (DROP_SLOT
    elsewhere) and ``v`` its amplified value; the compact ``(values, idx)``
    buffers are the first ``k_cap`` survivors in coordinate order, which
    a rank select over ``slot != DROP_SLOT`` finds and a gather of ``v``
    fills (``ops._two_pass``), so ranks past ``k_cap`` are dropped.
    ``ef=True`` additionally emits ``residual [r, c]`` = g minus
    what is sent: the values of the survivors ranked under ``k_cap``,
    rounded to ``wire_dtype`` (None when not requested)."""
    r, c = g.shape
    assert c == BLOCK_C, "two-pass kernels require the _pad_2d layout"
    grid = (r // BLOCK_R,)
    s1_2 = jnp.asarray(s1, jnp.float32).reshape(1, 1)
    s2_2 = jnp.asarray(s2, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0))
    out_specs = [tile, tile]
    out_shape = [jax.ShapeDtypeStruct((r, c), jnp.int32),
                 jax.ShapeDtypeStruct((r, c), jnp.float32)]
    if ef:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((r, c), g.dtype))
    out = pl.pallas_call(
        functools.partial(_compact_emit_body, pkind=pkind,
                          wire_dtype=wire_dtype or g.dtype, k_cap=k_cap),
        grid=grid,
        in_specs=[tile, tile, _smem(), _smem(),
                  pl.BlockSpec((BLOCK_C, BLOCK_C), lambda i: (0, 0)),
                  pl.BlockSpec((BLOCK_R, BLOCK_R), lambda i: (0, 0)),
                  _smem((2, grid[0]))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"compact_emit_{pkind}",
    )(g, u, s1_2, s2_2, up, lo, offsets)
    return out[0], out[1], (out[2] if ef else None)
