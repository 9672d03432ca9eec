"""jit'd public wrappers around the sparsify Pallas kernels: flatten/pad any
-shaped gradient leaf into the kernel's [R, C] block layout, run, unpad.

The end-to-end op ``gspar_sparsify`` performs Algorithm 3 (greedy) entirely
fused: one stats pass, ``num_iters`` saturation-aware tail-stats passes
driving the scalar rescale loop (skipped work when nothing saturates, since
the rescale factor is exactly 1 then), and one threshold-sample-scale pass.

The ``*_emit`` family is the two-pass compaction pipeline. Pass 1
(``select_stats_2d``) runs the selector and reduces per-tile survivor
counts and the p/variance accounting in one traversal; their exclusive
prefix sums are the tile offsets of pass 2 (``compact_emit_2d``), which
re-derives the kept mask, ranks every survivor and emits the optional EF
residual in the same pass. A rank select over the packed keep mask
finds the kept coordinates and one gather their values, which build the
compact ``(values, idx)`` buffers, and ``codec.encode`` runs on that
compact buffer exactly as on the reference backend. One emit wrapper per
selector:
``gspar_emit`` (Algorithm 3), ``closed_emit`` (Algorithm 2's lambda via
one XLA sort, then the same fused sample+write), ``unisp_emit``,
``bern_emit``, ``topk_emit``. The legacy ``gspar_sparse(_ef)`` wrappers
now route through the same pipeline.

Every emit wrapper is rank-polymorphic over a leading batch when driven
through ``jax.vmap`` — the shape-bucketed tree plan
(repro.core.grouping) relies on this to run one batched emit per shape
group instead of one dispatch per leaf, so keep new wrappers free of
Python-level branching on values and of shape-dependent side outputs.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import compaction
from repro.core import codecs as codecs_lib
from repro.core import sparsify as sparsify_lib
from repro.core.stages import stage
from repro.kernels.sparsify import kernel as K


def _pad_2d(flat: jax.Array) -> tuple[jax.Array, int, int, int]:
    n = flat.shape[0]
    c = K.BLOCK_C
    rows = -(-n // c)
    rows_pad = -(-rows // K.BLOCK_R) * K.BLOCK_R
    padded = jnp.zeros((rows_pad * c,), flat.dtype).at[:n].set(flat)
    return padded.reshape(rows_pad, c), n, rows_pad, c


@functools.partial(jax.jit, static_argnames=("interpret",))
def gspar_stats(g: jax.Array, interpret: bool = False):
    """(sum|g|, sum g^2, max|g|) — fused single pass."""
    g2d, _, _, _ = _pad_2d(g.reshape(-1))
    return K.stats_2d(g2d, interpret=interpret)


def _safe_div(num, den):
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def greedy_lambda(l1: jax.Array, mx: jax.Array, rho: float, d: int,
                  num_iters: int = 2,
                  tail_fn: Callable | None = None) -> jax.Array:
    """Algorithm 3's scalar fixed point from gradient statistics.

    Throughout the greedy iteration the probability vector keeps the form
    p_i = min(lam * |g_i|, 1), so the per-coordinate rescale loop of
    ``sparsify.greedy_probabilities`` collapses to a scalar recurrence that
    only needs, per iteration, the count and l1-mass of the *active*
    (non-saturated) set {i : |g_i| < 1/lam}:

        lam_0 = rho * d / ||g||_1
        c_k   = max(1, (rho*d - (d - n_active)) / (lam_k * l1_active))
        lam_{k+1} = c_k * lam_k

    ``tail_fn(thresh) -> (n_below, l1_below)`` supplies those two numbers
    (kernel ``tail_stats_2d`` on the fused path, a jnp reduction in tests).
    When ``tail_fn`` is None or ``lam_0 * max|g| <= 1`` no coordinate
    saturates, every c_k is exactly 1, and lam_0 is already the fixed point;
    the previous implementation stopped there unconditionally, which
    under-delivers density (and over-weights the surviving tail) whenever
    ``lam * max|g| > 1``.
    """
    d_f = jnp.float32(d)
    rho_d = jnp.asarray(rho, jnp.float32) * d_f   # d may exceed int32
    lam0 = _safe_div(rho_d, jnp.asarray(l1, jnp.float32))
    if tail_fn is None or num_iters <= 0:
        return lam0

    def rescale(lam):
        for _ in range(num_iters):
            n_below, l1_below = tail_fn(_safe_div(jnp.float32(1.0), lam))
            target = rho_d - (d_f - n_below)
            c = _safe_div(target, lam * l1_below)
            c = jnp.maximum(c, 1.0)              # c <= 1 -> converged (no-op)
            lam = c * lam
        return lam

    # mx gates the tail-stats passes entirely: lam0 * max|g| <= 1 means no
    # coordinate saturates and lam0 is already the fixed point.
    return jax.lax.cond(lam0 * jnp.asarray(mx, jnp.float32) <= 1.0,
                        lambda lam: lam, rescale, lam0)


def _kernel_tail_fn(g2d: jax.Array, n: int, interpret: bool) -> Callable:
    """tail_stats over the padded layout, corrected for the zero padding
    (each pad slot counts as an active coordinate with zero mass)."""
    pad = g2d.size - n

    def tail(thresh):
        n_below, l1_below = K.tail_stats_2d(g2d, thresh, interpret=interpret)
        return n_below - jnp.float32(pad), l1_below
    return tail


@functools.partial(jax.jit, static_argnames=("rho", "num_iters", "interpret"))
def gspar_lambda(g: jax.Array, rho: float = 0.1, num_iters: int = 2,
                 interpret: bool = False) -> jax.Array:
    """Saturation-aware greedy lambda for a leaf, via the fused stats path."""
    g2d, n, _, _ = _pad_2d(g.reshape(-1))
    l1, mx = K.stats_l1max_2d(g2d, interpret=interpret)
    return greedy_lambda(l1, mx, rho, n, num_iters,
                         tail_fn=_kernel_tail_fn(g2d, n, interpret))


@functools.partial(jax.jit, static_argnames=("rho", "num_iters", "interpret"))
def gspar_sparsify(g: jax.Array, u: jax.Array, rho: float = 0.1,
                   num_iters: int = 2, interpret: bool = False) -> jax.Array:
    """End-to-end fused Q(g) with pregenerated uniforms u (paper 5.3 trick)."""
    shape = g.shape
    flat = g.reshape(-1)
    g2d, n, _, _ = _pad_2d(flat)
    u2d, _, _, _ = _pad_2d(u.reshape(-1).astype(jnp.float32))
    l1, mx = K.stats_l1max_2d(g2d, interpret=interpret)
    lam = greedy_lambda(l1, mx, rho, n, num_iters,
                        tail_fn=_kernel_tail_fn(g2d, n, interpret))
    out = K.sparsify_2d(g2d, u2d, lam, interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)


class EmitResult(NamedTuple):
    """Wire buffers and accounting scalars from the two-pass pipeline.

    ``values``/``idx`` are the compact buffers (values codec-encoded in the
    wire dtype, idx the ascending-coordinate valid prefix, padding slots
    idx 0 / value exactly 0). ``nnz`` counts survivors (pre-cap),
    ``p_sum``/``den`` the accounting reductions (sum p, sum g^2) that
    would otherwise cost the backend an extra O(d) pass, ``scale`` the
    codec's per-message scale, and ``residual`` the in-pass EF residual
    (else None)."""
    values: jax.Array
    idx: jax.Array
    nnz: jax.Array
    p_sum: jax.Array
    den: jax.Array
    scale: jax.Array
    residual: jax.Array | None


_F32 = codecs_lib.FloatCodec()


def _keep_words(slot: jax.Array) -> jax.Array:
    """The keep mask of a slot map ``[r, c]`` packed LSB-first into one row
    of uint32 words ``[1, r * c / 32]``: word w holds coordinates 32w ..
    32w + 31.

    One matmul packs it: the 0/1 mask in bfloat16 times a ``[c, 2c/32]``
    matrix of powers of two sums bits 0-15 and bits 16-31 of every word
    as floats under 2**16, which the float32 accumulator holds exactly.
    Strided slices (one per bit) made the compiler materialise the mask
    and each slice, eight bytes a coordinate, where the matmul's output
    takes one."""
    r, c = slot.shape
    n = c // compaction.WORD_BITS
    half = compaction.WORD_BITS // 2
    col = np.arange(c)
    bit = col % compaction.WORD_BITS
    weights = np.zeros((c, 2 * n), np.float32)
    weights[col, col // compaction.WORD_BITS + n * (bit >= half)] = \
        2.0 ** (bit % half)
    keep = (slot != K.DROP_SLOT).astype(jnp.bfloat16)
    halves = jnp.dot(keep, jnp.asarray(weights, jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.uint32)
    return (halves[:, :n] | (halves[:, n:] << half)).reshape(1, -1)


def _two_pass(flat: jax.Array, u: jax.Array | None, s1, s2, *, pkind: str,
              codec, k_cap: int, ef: bool, u_cod: jax.Array | None,
              interpret: bool) -> EmitResult:
    """Shared two-pass driver: pass 1 per-tile counts, exclusive tile
    offsets, pass 2 ranks, then the compact select and the codec encode.
    ``u`` is the selector's pregenerated uniforms (ignored for
    deterministic selectors), ``u_cod`` the codec's (length k_cap, one per
    compact rank).

    The compact select never touches a coordinate one by one: the keep
    mask (``slot != DROP_SLOT``) packs into words, the rank histogram of
    ``compaction.first_zero_bits`` finds the first k_cap kept coordinates
    in ascending order from the complemented words (ranks past k_cap are
    never produced), and one k_cap gather reads their values. Its cost
    grows with d/32 + k_cap, not with d."""
    g2d, n, _, _ = _pad_2d(flat)
    if u is not None:
        u2d, _, _, _ = _pad_2d(u.reshape(-1).astype(jnp.float32))
    else:
        u2d = g2d                               # unused by the kernel body
    up, lo = K.prefix_operands()
    tiles, psum, den = K.select_stats_2d(g2d, u2d, s1, s2, up, lo,
                                         pkind=pkind, interpret=interpret)
    offsets = jnp.cumsum(tiles, axis=1) - tiles          # exclusive
    wire_dtype = codec.wire_dtype(flat.dtype)
    with stage("compact"):
        slot, v, res = K.compact_emit_2d(g2d, u2d, s1, s2, offsets, up, lo,
                                         pkind=pkind, k_cap=k_cap,
                                         wire_dtype=wire_dtype, ef=ef,
                                         interpret=interpret)
        coord = compaction.first_zero_bits(~_keep_words(slot), k_cap,
                                           slot.size)[0]
        live = coord < slot.size
        idx = jnp.where(live, coord, 0)
        # coord ascends, its tail (slot.size) clamped to the last coordinate;
        # gathered from the 2-D v: flattening it first copies the leaf
        at = jnp.minimum(coord, slot.size - 1)
        vals = jnp.where(live, v.at[at // v.shape[1], at % v.shape[1]].get(
            indices_are_sorted=True, mode="promise_in_bounds"), 0.0)
        scale = codec.scale(vals)
        values = codec.encode(vals, scale, u_cod).astype(wire_dtype)
    if ef:
        res = res.reshape(-1)[:n]
    return EmitResult(values, idx, jnp.sum(tiles[0]), psum, den, scale, res)


_EMIT_STATICS = ("k_cap", "codec", "ef", "interpret")


@functools.partial(jax.jit,
                   static_argnames=_EMIT_STATICS + ("rho", "num_iters"))
def gspar_emit(g: jax.Array, u: jax.Array, u_cod: jax.Array | None = None, *,
               k_cap: int, rho: float = 0.1, num_iters: int = 2,
               codec=_F32, ef: bool = False,
               interpret: bool = False):
    """Algorithm 3 (greedy lambda), fully fused: stats -> scalar lambda ->
    two-pass compact emit. Returns ``(EmitResult, lam)``."""
    flat = g.reshape(-1)
    g2d, n, _, _ = _pad_2d(flat)
    l1, mx = K.stats_l1max_2d(g2d, interpret=interpret)
    lam = greedy_lambda(l1, mx, rho, n, num_iters,
                        tail_fn=_kernel_tail_fn(g2d, n, interpret))
    er = _two_pass(flat, u, lam, jnp.float32(0), pkind="lam", codec=codec,
                   k_cap=k_cap, ef=ef, u_cod=u_cod,
                   interpret=interpret)
    return er, lam


@functools.partial(jax.jit, static_argnames=_EMIT_STATICS + ("eps",))
def closed_emit(g: jax.Array, u: jax.Array, u_cod: jax.Array | None = None, *,
                k_cap: int, eps: float = 0.1, codec=_F32, ef: bool = False,
                interpret: bool = False):
    """Algorithm 2 (closed-form lambda: one XLA sort for the scalar, shared
    with the reference solver bit-for-bit), then the same fused sample +
    compact write as the greedy path. Returns ``(EmitResult, lam)``."""
    flat = g.reshape(-1)
    lam, _any_ok = sparsify_lib.closed_form_lambda(flat, eps)
    er = _two_pass(flat, u, lam, jnp.float32(0), pkind="lam", codec=codec,
                   k_cap=k_cap, ef=ef, u_cod=u_cod,
                   interpret=interpret)
    return er, lam


@functools.partial(jax.jit, static_argnames=_EMIT_STATICS + ("rho",))
def unisp_emit(g: jax.Array, u: jax.Array, u_cod: jax.Array | None = None, *,
               k_cap: int, rho: float = 0.1, codec=_F32, ef: bool = False,
               interpret: bool = False):
    """UniSp baseline: p = rho on the support. Returns an ``EmitResult``."""
    return _two_pass(g.reshape(-1), u, jnp.float32(rho), jnp.float32(0),
                     pkind="rho", codec=codec, k_cap=k_cap, ef=ef,
                     u_cod=u_cod, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_EMIT_STATICS)
def bern_emit(g: jax.Array, u: jax.Array, u_cod: jax.Array | None = None, *,
              k_cap: int, codec=_F32, ef: bool = False,
              interpret: bool = False):
    """Bernoulli selector (TernGrad's): p = |g| / max|g|. Returns
    ``(EmitResult, max_abs)``."""
    flat = g.reshape(-1)
    g2d, _, _, _ = _pad_2d(flat)
    _, mx = K.stats_l1max_2d(g2d, interpret=interpret)
    er = _two_pass(flat, u, jnp.float32(0), mx, pkind="bern", codec=codec,
                   k_cap=k_cap, ef=ef, u_cod=u_cod,
                   interpret=interpret)
    return er, mx


@functools.partial(jax.jit, static_argnames=_EMIT_STATICS + ("k_target",))
def topk_emit(g: jax.Array, u_cod: jax.Array | None = None, *, k_cap: int,
              k_target: int, codec=_F32, ef: bool = False,
              interpret: bool = False):
    """Deterministic top-k: one XLA ``top_k`` derives the magnitude
    threshold and the at-threshold tie budget; the kernel then keeps
    |g| > t plus the first ``budget`` coordinates with |g| == t, which is
    exactly XLA top_k's lowest-index-first tie break — so the kept set
    matches the reference selector while the compact write stays a
    counting pass. Returns an ``EmitResult``."""
    flat = g.reshape(-1)
    a = jnp.abs(flat.astype(jnp.float32))
    topv = jax.lax.top_k(a, k_target)[0]
    t = topv[-1]
    budget = jnp.float32(k_target) - (jnp.count_nonzero(topv > t)
                                      .astype(jnp.float32))
    return _two_pass(flat, None, t, budget, pkind="topk", codec=codec,
                     k_cap=k_cap, ef=ef, u_cod=u_cod,
                     interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("rho", "num_iters", "k_cap", "interpret",
                                    "out_dtype"))
def gspar_sparse(g: jax.Array, u: jax.Array, k_cap: int, rho: float = 0.1,
                 num_iters: int = 2, interpret: bool = False,
                 out_dtype=None):
    """Fused stats -> lambda -> sample -> compact: emits the wire buffers
    ``(values[k_cap], idx[k_cap], nnz, lam)`` directly.

    Compatibility wrapper over ``gspar_emit``: the compaction is the
    two-pass counting write (first k_cap survivors in coordinate order) —
    sort-free, unlike magnitude-ranked ``top_k`` compaction. Bernoulli
    survivors are exchangeable, so dropping by position on (rare) overflow
    is as unbiased as dropping by magnitude is biased; overflow itself
    stays ~impossible at the configured capacity slack. Padding slots
    carry idx 0 with value exactly 0, so scatter-add reconstruction is
    unaffected.

    The ascending-coordinate order of the valid prefix is a load-bearing
    contract (``SparseGrad.idx_sorted``): the BITMAP wire layout packs
    these buffers without an argsort (``compaction.bitmap_pack(nnz=...)``),
    keeping the fused path's wire prep O(k_cap).

    ``out_dtype`` (static) selects the float wire dtype: the compact write
    quantizes kept values on its way out of VMEM, so e.g. the bf16 codec
    costs no extra traversal.
    """
    codec = _codec_for(out_dtype)
    er, lam = gspar_emit(g, u, None, k_cap=k_cap, rho=rho,
                         num_iters=num_iters, codec=codec,
                         interpret=interpret)
    return er.values, er.idx, er.nnz, lam


def _codec_for(out_dtype):
    if out_dtype is None:
        return _F32
    if jnp.dtype(out_dtype) == jnp.bfloat16:
        return codecs_lib.FloatCodec(bits=16, rounding=True)
    raise NotImplementedError(
        f"gspar_sparse out_dtype {out_dtype!r}: only None (leaf dtype) and "
        "bfloat16 ride the compat wrapper; use gspar_emit with a codec")


@functools.partial(jax.jit,
                   static_argnames=("rho", "num_iters", "k_cap", "interpret",
                                    "out_dtype"))
def gspar_sparse_ef(g: jax.Array, u: jax.Array, k_cap: int, rho: float = 0.1,
                    num_iters: int = 2, interpret: bool = False,
                    out_dtype=None):
    """Error-feedback twin of ``gspar_sparse``: the compact-write kernel
    subtracts the kept (amplified, wire-dtype-rounded) values from the
    target in the same pass that samples them, emitting ``(values[k_cap],
    idx[k_cap], nnz, lam, residual[d])`` with ``residual = g - Q(g)`` in
    g's dtype and values in ``out_dtype`` (the codec's wire dtype; the
    in-pass subtraction therefore charges the wire rounding of kept values
    to the residual with no post-hoc fold). On overflow (nnz > k_cap) the
    survivors past the capacity are sampled but not transmitted, and the
    residual carries them whole: ``residual = g - transmitted``, the
    reference sparse backend's rule. ``capacity_for`` sizes k_cap at 1.25x
    the mean draw, which a row of a few thousand coordinates can exceed."""
    codec = _codec_for(out_dtype)
    er, lam = gspar_emit(g, u, None, k_cap=k_cap, rho=rho,
                         num_iters=num_iters, codec=codec, ef=True,
                         interpret=interpret)
    return er.values, er.idx, er.nnz, lam, er.residual


@functools.partial(jax.jit, static_argnames=("rho", "num_iters", "interpret"))
def gspar_sparsify_prng(g: jax.Array, seed: jax.Array, rho: float = 0.1,
                        num_iters: int = 2, interpret: bool = False) -> jax.Array:
    """Production variant: on-core PRNG, no uniform input buffer.

    interpret=True runs the kernel under the TPU-interpret emulator
    (pltpu.InterpretParams): the plain CPU interpreter has no lowering for
    the TPU PRNG primitives. The emulator's prng_random_bits yields zero
    bits (randomness is a hardware property), i.e. u == 0 and every
    coordinate with p > 0 is kept."""
    from jax.experimental.pallas import tpu as pltpu
    shape = g.shape
    g2d, n, _, _ = _pad_2d(g.reshape(-1))
    l1, mx = K.stats_l1max_2d(g2d, interpret=interpret)
    lam = greedy_lambda(l1, mx, rho, n, num_iters,
                        tail_fn=_kernel_tail_fn(g2d, n, interpret))
    prng_interp = pltpu.InterpretParams() if interpret else False
    out = K.sparsify_prng_2d(g2d, lam, seed, interpret=prng_interp)
    return out.reshape(-1)[:n].reshape(shape)
