"""Wire-format v3 (RICE layout) tests: the Golomb-Rice delta-coded index
stream on the real collective.

  * codec edge cases: k = 0 (all-dead) leaves, k_cap = d leaves,
    single-element streams, adversarial max-delta gaps (which exactly hit
    the static capacity bound), r = 0, d not a multiple of 32
  * property: realized encoder word counts == the coding model's
    prediction (``coding.rice_stream_words``), and always <= the static
    capacity the chooser priced (``coding.rice_wire_words``)
  * sorted (argsort-free, ``SparseGrad.idx_sorted``) path == generic path
  * the static parameter rule and the chooser's RICE regime
  * dense-vs-gather bit-identity under ``--wire-layout rice`` on BOTH
    backends, with and without error feedback
  * SyncStats.wire_bytes under forced rice == values + TRUE encoded words
    + the phase-one counts vector + scales — never the padded capacity
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import compaction, wire_layout
from repro.core import coding
from repro.core.api import CompressionConfig, compress_tree_sparse
from repro.comm.sync import sync_tree


def _sparse_leaf(rng, d, n_live, k_cap):
    """A compact (values, idx, nnz) triple with n_live random coords."""
    q = np.zeros(d, np.float32)
    if n_live:
        nz = rng.choice(d, n_live, replace=False)
        q[nz] = np.where(rng.random(n_live) < 0.5, 1.0, -1.0) * (
            1.0 + rng.random(n_live)).astype(np.float32)
    vals, idx, nnz = compaction.compact(jnp.asarray(q), k_cap)
    return q, vals, idx, nnz


def _roundtrip(vals, idx, d, r, nnz=None):
    sv, w, used = compaction.rice_encode(vals, idx, d, r, nnz=nnz)
    dec = compaction.rice_decode(w, vals.shape[-1], d, r)
    sv_np, dec_np = np.asarray(sv), np.asarray(dec)
    rec = np.zeros(d, np.float32)
    live = sv_np != 0
    rec[dec_np[live]] = sv_np[live]
    return rec, int(used), w


class TestRiceCodecEdgeCases:
    @pytest.mark.parametrize("d,density", [(70, 0.3), (1000, 0.05),
                                           (4096, 0.1), (1 << 16, 0.01)])
    def test_roundtrip_exact(self, d, density):
        rng = np.random.default_rng(d)
        k_cap = min(d, max(128, -(-int(d * density) // 128) * 128))
        q, vals, idx, _ = _sparse_leaf(rng, d, int(d * density), k_cap)
        r = coding.rice_parameter(k_cap, d)
        rec, used, _ = _roundtrip(vals, idx, d, r)
        np.testing.assert_array_equal(rec, q)
        assert used <= compaction.rice_cap_words(k_cap, d, r)

    def test_k0_all_dead_leaf(self):
        """nnz = 0: every slot codes a zero quotient; the stream is exactly
        k_cap * (r + 1) bits and reconstructs to all-zeros."""
        d, k_cap, r = 1 << 12, 128, 4
        vals = jnp.zeros((k_cap,), jnp.float32)
        idx = jnp.zeros((k_cap,), jnp.int32)
        rec, used, _ = _roundtrip(vals, idx, d, r)
        np.testing.assert_array_equal(rec, np.zeros(d, np.float32))
        assert used == -(-(k_cap * (r + 1)) // 32)
        assert used == coding.rice_stream_words([], k_cap, d, r)

    def test_kcap_equals_d_full_leaf(self):
        """k_cap = d with every coordinate live: all gaps are 1, quotients
        all 0 at r = 0 — the stream degenerates to d+... terminator bits
        (the regime the chooser hands to DENSE, but the codec must still
        be exact under a forced override)."""
        d = 256
        rng = np.random.default_rng(0)
        q = (rng.standard_normal(d).astype(np.float32)
             + np.sign(rng.standard_normal(d)).astype(np.float32) * 2)
        assert np.all(q != 0)
        vals, idx, _ = compaction.compact(jnp.asarray(q), d)
        r = coding.rice_parameter(d, d)
        assert r == 0
        rec, used, _ = _roundtrip(vals, idx, d, r)
        np.testing.assert_array_equal(rec, q)
        assert used == coding.rice_stream_words(np.arange(d), d, d, r)

    def test_single_element_stream(self):
        d, k_cap = 4096, 1
        for coord in (0, 1, d - 1):
            vals = jnp.asarray([1.5], jnp.float32)
            idx = jnp.asarray([coord], jnp.int32)
            r = coding.rice_parameter(k_cap, d)
            rec, used, _ = _roundtrip(vals, idx, d, r)
            expect = np.zeros(d, np.float32)
            expect[coord] = 1.5
            np.testing.assert_array_equal(rec, expect)
            assert used == coding.rice_stream_words([coord], k_cap, d, r)

    def test_adversarial_max_delta_hits_capacity_exactly(self):
        """One live coordinate at d-1: the unary quotient is the whole
        (d-1) >> r mass — the worst case the capacity bound prices. The
        encoder must land exactly on the bound, never beyond."""
        d, k_cap = 1 << 16, 128
        vals = jnp.zeros((k_cap,), jnp.float32).at[0].set(2.5)
        idx = jnp.zeros((k_cap,), jnp.int32).at[0].set(d - 1)
        for r in (0, 3, 8, coding.rice_parameter(k_cap, d)):
            rec, used, _ = _roundtrip(vals, idx, d, r)
            expect = np.zeros(d, np.float32)
            expect[d - 1] = 2.5
            np.testing.assert_array_equal(rec, expect)
            assert used == compaction.rice_cap_words(k_cap, d, r)
            assert used == coding.rice_stream_words([d - 1], k_cap, d, r)

    def test_r0_and_ragged_word_tail(self):
        """r = 0 (pure unary) on a d that is not a multiple of 32."""
        d = 70
        q = np.zeros(d, np.float32)
        for c in (0, 31, 32, 63, 69):
            q[c] = float(c + 1)
        vals, idx, _ = compaction.compact(jnp.asarray(q), 64)
        rec, used, _ = _roundtrip(vals, idx, d, 0)
        np.testing.assert_array_equal(rec, q)
        assert used == coding.rice_stream_words([0, 31, 32, 63, 69],
                                                64, d, 0)

    def test_sorted_path_matches_generic_with_codec_zeroed_levels(self):
        """The argsort-free encode (counting-compacted buffers + nnz) must
        reconstruct identically to the generic path even when an integer
        codec zeroed a mid-prefix level — the zeroed coordinate's code
        simply decodes to a zero-valued (hence dropped) slot."""
        d, r = 100, 1
        vals = jnp.asarray([5, -1, 0, 7, 0, 0], jnp.int8)
        idx = jnp.asarray([2, 31, 33, 64, 0, 0], jnp.int32)
        expect = np.zeros(d, np.int8)
        expect[2], expect[31], expect[64] = 5, -1, 7
        for nnz in (None, jnp.asarray(4, jnp.int32)):
            sv, w, _ = compaction.rice_encode(vals, idx, d, r, nnz=nnz)
            dec = np.asarray(compaction.rice_decode(w, 6, d, r))
            svn = np.asarray(sv)
            rec = np.zeros(d, np.int8)
            rec[dec[svn != 0]] = svn[svn != 0]
            np.testing.assert_array_equal(rec, expect)

    def test_stacked_vmap_roundtrip(self):
        d, layers, k_cap, r = 512, 4, 128, 2
        rng = np.random.default_rng(5)
        q = np.where(rng.random((layers, d)) < 0.1,
                     rng.standard_normal((layers, d)), 0.0).astype(np.float32)
        vals, idx, _ = jax.vmap(lambda row: compaction.compact(row, k_cap))(
            jnp.asarray(q))
        sv, w, used = jax.jit(jax.vmap(
            lambda v, i: compaction.rice_encode(v, i, d, r)))(vals, idx)
        dec = compaction.rice_decode(w, k_cap, d, r)   # batched decode
        for layer in range(layers):
            svn = np.asarray(sv[layer])
            rec = np.zeros(d, np.float32)
            live = svn != 0
            rec[np.asarray(dec[layer])[live]] = svn[live]
            np.testing.assert_array_equal(rec, q[layer])
            assert int(used[layer]) <= compaction.rice_cap_words(k_cap, d, r)


def _bit_pack_gaps(x, r, cap_words):
    """Bit-level reference of ``compaction._rice_pack_gaps``: one element
    per stream bit, packed 32 to a word."""
    k = x.shape[0]
    q = x >> r
    if r > 0:
        rp = np.arange(k * r)
        rbits = (x[rp // r] >> (rp % r)) & 1
    else:
        rbits = np.zeros(0, np.int64)
    u_cap = cap_words * 32 - k * r
    tpos = np.cumsum(q) + np.arange(k)
    total = int(q.sum()) + k
    tmark = np.zeros(u_cap, bool)
    tmark[tpos[tpos < u_cap]] = True
    ubits = (np.arange(u_cap) < total) & ~tmark
    bits = np.concatenate([rbits, ubits]).astype(np.uint64)
    words = (bits.reshape(-1, 32) << np.arange(32, dtype=np.uint64)).sum(1)
    return (words.astype(np.uint32).view(np.int32),
            (k * r + total + 31) // 32)


def _bit_decode(words, k, r):
    """Bit-level reference of ``compaction.rice_decode`` for one message:
    the i-th code's terminator is the (i+1)-th zero bit of the unary
    field (``u_cap`` when the message holds fewer zeros)."""
    u = np.asarray(words).view(np.uint32).astype(np.uint64)
    bits = ((u[:, None] >> np.arange(32, dtype=np.uint64)) & 1).reshape(-1)
    rem = ((bits[:k * r].reshape(k, r) << np.arange(r, dtype=np.uint64))
           .sum(1).astype(np.int64) if r else np.zeros(k, np.int64))
    ub = bits[k * r:]
    z = np.flatnonzero(ub == 0)
    zpos = np.full(k, ub.size, np.int64)
    zpos[:min(k, z.size)] = z[:k]
    q = zpos - np.concatenate([[-1], zpos[:-1]]) - 1
    # int32 wrap-around, as the decoder's own arithmetic
    return (np.cumsum(((q << r) | rem) + 1) - 1).astype(np.int32)


class TestWordLevelCodec:
    """The word-level Rice pack and decode against bit-level references
    that follow the stream layout one bit at a time."""

    pack = staticmethod(jax.jit(compaction._rice_pack_gaps,
                                static_argnums=(1, 2)))
    decode = staticmethod(jax.jit(compaction.rice_decode,
                                  static_argnums=(1, 2, 3)))

    @pytest.mark.parametrize("d,k_cap,r", [(70, 64, 0), (1000, 128, 2),
                                           (4096, 256, 3), (1 << 16, 640, 6),
                                           (5000, 128, 30)])
    def test_pack_matches_bit_level(self, d, k_cap, r):
        rng = np.random.default_rng(d + r)
        for n_live in (0, 1, k_cap // 3, k_cap):
            coords = np.sort(rng.choice(d, min(n_live, d), replace=False))
            sidx = np.full(k_cap, d, np.int32)
            sidx[:coords.size] = coords
            x = np.asarray(compaction._rice_gaps(jnp.asarray(sidx), d))
            cap = compaction.rice_cap_words(k_cap, d, r)
            words, used = self.pack(jnp.asarray(x), r, cap)
            ref_words, ref_used = _bit_pack_gaps(x.astype(np.int64), r, cap)
            np.testing.assert_array_equal(np.asarray(words), ref_words)
            assert int(used) == ref_used

    @pytest.mark.parametrize("k_cap,r,n_words,batch,fill", [
        pytest.param(64, 0, 9, (3, 2), "random", id="64-0-9"),
        pytest.param(100, 3, 31, (3, 2), "random", id="100-3-31"),
        pytest.param(37, 5, 12, (3, 2), "random", id="37-5-12"),
        pytest.param(8, 30, 9, (3, 2), "random", id="8-30-9"),
        # k_cap off a multiple of 32, 7-bit fields straddling words
        pytest.param(45, 7, 20, (3, 2), "random", id="45-7-20"),
        # one bit in eight set: far more zeros than codes
        pytest.param(40, 2, 64, (3, 2), "sparse", id="40-2-64-sparse"),
        # the zeroed-header skip sentinel
        pytest.param(70, 3, 16, (3, 2), "zero", id="70-3-16-zero"),
        pytest.param(96, 4, 40, (4, 3), "random", id="96-4-40-batch4x3"),
        # encoder output ending inside its last word, zero padding after
        pytest.param(50, 0, 12, (3, 2), "encoded", id="50-0-12-ragged"),
    ])
    def test_decode_matches_bit_level_on_any_words(self, k_cap, r, n_words,
                                                   batch, fill):
        """Random words (valid or not, too few zeros included) decode as
        the bit-level reference does, batched and unbatched."""
        rng = np.random.default_rng(k_cap * 31 + r)
        shape = batch + (n_words,)
        words = rng.integers(-2**31, 2**31, shape, dtype=np.int64)
        if fill == "random":
            words[0, 0] = -1                         # no zero bit at all
            words[0, 1, :n_words // 2] = 0           # a run of zero words
        elif fill == "sparse":
            bits = rng.random(shape + (32,)) < 0.125
            words = (bits << np.arange(32)).sum(-1)
        elif fill == "zero":
            words[:] = 0
        elif fill == "encoded":
            for b in np.ndindex(batch):
                x = rng.integers(0, 8, k_cap)
                w, used = _bit_pack_gaps(x, r, n_words)
                assert 0 < (k_cap * r + x.sum() + k_cap) % 32   # ragged
                words[b] = w
                assert not words[b][used:].any()
        words = words.astype(np.uint32).view(np.int32)
        got = np.asarray(self.decode(jnp.asarray(words), k_cap, 0, r))
        for b in np.ndindex(words.shape[:-1]):
            np.testing.assert_array_equal(got[b],
                                          _bit_decode(words[b], k_cap, r),
                                          err_msg=str(b))
        if fill == "zero":
            np.testing.assert_array_equal(
                got, np.broadcast_to(np.arange(k_cap), got.shape))
        one = np.asarray(self.decode(jnp.asarray(words[1, 1]), k_cap, 0, r))
        np.testing.assert_array_equal(one, got[1, 1])

    @pytest.mark.parametrize("r", [0, 3])
    def test_decode_cost_does_not_grow_with_the_stream(self, r):
        """No search over the unary region: the decoder traces to the
        same number of gathers (at most one) at 64 and at 65536 unary
        words, and to no loop."""
        k_cap = 4096

        def primitives(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (list, tuple)) else (v,):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from primitives(sub)

        def count(n_words):
            spec = jax.ShapeDtypeStruct((2, n_words), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda w: compaction.rice_decode(w, k_cap, 0, r))(spec)
            names = list(primitives(jaxpr.jaxpr))
            assert "while" not in names
            return names.count("gather")

        small = count(k_cap * r // 32 + 64)
        assert small == count(65536) and small <= 1


class TestRealizedEqualsModel:
    def test_encoder_words_match_coding_model(self):
        """Property sweep: the encoder's used-word count == the coding
        model's word prediction for the same live coordinate set, and
        both <= the static capacity the chooser priced."""
        rng = np.random.default_rng(7)
        for _ in range(60):
            d = int(rng.integers(64, 1 << 16))
            k_cap = int(min(d, rng.integers(1, 1024)))
            n_live = int(rng.integers(0, k_cap + 1))
            _, vals, idx, _ = _sparse_leaf(rng, d, n_live, k_cap)
            r = coding.rice_parameter(k_cap, d)
            _, w, used = compaction.rice_encode(vals, idx, d, r)
            live = np.asarray(vals) != 0
            live_idx = np.asarray(idx)[live]
            assert int(used) == coding.rice_stream_words(live_idx, k_cap, d)
            assert int(used) <= coding.rice_wire_words(k_cap, d), \
                (d, k_cap, n_live)

    def test_parameter_rule(self):
        """2^r ~= ln2 * d/k_cap, clipped to [0, RICE_MAX_R]; part of the
        wire format (docs/WIRE_FORMAT.md) — sender and receiver derive it
        independently."""
        assert coding.rice_parameter(128, 128) == 0          # mu = 1
        assert coding.rice_parameter(128, 512) == 1          # m_opt ~ 2.77
        assert coding.rice_parameter(128, 1 << 20) == 12     # m_opt ~ 5681
        assert coding.rice_parameter(1, 1 << 30) <= compaction.RICE_MAX_R

    def test_chooser_prices_rice_at_capacity(self):
        """realized_wire_bits('rice') == k_cap * vb + capacity words * 32 —
        the worst case, so a chosen RICE leaf can never realize more bytes
        than the layout it displaced."""
        for (k_cap, d, vb) in [(128, 1 << 16, 32), (896, 1 << 16, 16),
                               (3328, 1 << 18, 32)]:
            got = coding.realized_wire_bits("rice", k_cap, d, vb)
            assert got == (k_cap * vb
                           + coding.rice_wire_words(k_cap, d) * 32)


def _grad_tree(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal(4096)
                         * np.exp(rng.standard_normal(4096)), jnp.float32),
        "stack": jnp.asarray(rng.standard_normal((3, 2048)), jnp.float32),
        "tiny": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }


STACKED = {"w": False, "stack": True, "tiny": False}


def _sync(cfg, key, grads, residual=None):
    mesh = jax.make_mesh((1,), ("data",))
    args = (key, grads) + ((residual,) if residual is not None else ())

    def step(k, g, *r):
        return sync_tree(cfg, k, g, data_axis="data", stacked=STACKED,
                         feedback=r[0] if r else None)

    with jax.set_mesh(mesh):
        fn = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(),) * len(args),
            out_specs=(P(),) * 3, axis_names={"data"}, check_vma=False))
        return fn(*args)


class TestRiceOnTheWire:
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    @pytest.mark.parametrize("name", ["gspar", "gspar+qsgd8", "unisp",
                                      "topk+ternary"])
    def test_dense_vs_gather_bit_identical_forced_rice(self, name, backend):
        """The acceptance bar, per backend contract: on the reference
        backend --wire-layout rice keeps the gather wire bit-identical to
        the dense psum (they share one scheme computation); on pallas the
        fused kernel's lambda legitimately differs from the reference
        solver by an ulp (the dense wire always compresses via the
        reference scheme, so selection boundaries can flip near the
        threshold — test_backend compares jointly-selected coordinates
        only), so the established equivalence is layout-INVARIANCE: rice
        bit-identical to the coo gather of the same backend."""
        grads = _grad_tree(0)
        key = jax.random.key(3)
        kw = dict(rho=0.05, min_leaf_size=64, backend=backend,
                  capacity_slack=4.0)
        ref, _, _ = _sync(CompressionConfig(name=name, wire="dense", **kw),
                          key, grads)
        got, _, stats = _sync(
            CompressionConfig(name=name, wire="gather", wire_layout="rice",
                              **kw), key, grads)
        if backend == "reference":
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
        else:
            coo, _, _ = _sync(
                CompressionConfig(name=name, wire="gather",
                                  wire_layout="coo", **kw), key, grads)
            for a, b in zip(jax.tree.leaves(coo), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(stats.wire_bytes) > 0

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_error_feedback_bit_identical_on_rice(self, backend):
        """EF residuals are computed upstream of the wire layout; forcing
        rice must keep params AND residual equal to the dense wire's
        (reference) / the coo gather's (pallas — same backend contract as
        above)."""
        grads = _grad_tree(2)
        key = jax.random.key(9)
        res0 = jax.tree.map(jnp.zeros_like, grads)
        kw = dict(name="gspar+qsgd8", rho=0.05, min_leaf_size=64,
                  backend=backend, capacity_slack=4.0, error_feedback=True)
        base_cfg = (CompressionConfig(wire="dense", **kw)
                    if backend == "reference" else
                    CompressionConfig(wire="gather", wire_layout="coo",
                                      **kw))
        sd, rd, _ = _sync(base_cfg, key, grads, residual=res0)
        sg, rg, _ = _sync(CompressionConfig(wire="gather",
                                            wire_layout="rice", **kw),
                          key, grads, residual=res0)
        for a, b in zip(jax.tree.leaves((sd, rd)), jax.tree.leaves((sg, rg))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wire_bytes_charge_true_lengths_not_padding(self):
        """SyncStats.wire_bytes under forced rice == k_cap value bytes +
        TRUE encoded index words + the phase-one counts vector + codec
        scales + the tiny-leaf psum — strictly under the static capacity
        accounting whenever the draw beats its own worst case."""
        grads = _grad_tree(4)
        key = jax.random.key(11)
        cfg = CompressionConfig(name="gspar+qsgd8", rho=0.05,
                                min_leaf_size=64, backend="reference",
                                capacity_slack=4.0, wire="gather",
                                wire_layout="rice")
        _, _, stats = _sync(cfg, key, grads)
        # replay the exact shipped message: sync_tree folds the worker
        # index into the key (worker 0 on this 1-device axis)
        items, _, _, _ = compress_tree_sparse(cfg,
                                              jax.random.fold_in(key, 0),
                                              grads, stacked=STACKED)
        expect = 0.0
        capacity = 0.0
        for kind, p, _ in items:
            if kind == "dense":
                expect += p.size * 4
                capacity += p.size * 4
                continue
            layers = p.values.shape[0] if p.values.ndim == 2 else 1
            lp = wire_layout.plan(p)
            _, _, used = wire_layout.pack(p, lp)
            expect += (p.k_cap * p.values.dtype.itemsize * layers
                       + 4 * float(jnp.sum(used))        # true payload
                       + 4 * layers                      # phase-one counts
                       + 4 * layers)                     # codec scales
            capacity += p.realized_wire_bits() / 8 + 8 * layers
        assert float(stats.wire_bytes) == pytest.approx(expect)
        assert float(stats.wire_bytes) < capacity

    def test_compress_tree_sparse_stamps_rice(self):
        """The backend stamps rice both when forced and when it is the
        argmin (low density), incl. the pallas counting path, whose sorted
        prefix encodes argsort-free."""
        g = {"w": _grad_tree(6)["w"]}
        for backend in ("reference", "pallas"):
            cfg = CompressionConfig(name="gspar", rho=0.01, wire="gather",
                                    min_leaf_size=8, backend=backend)
            items, _, _, _ = compress_tree_sparse(cfg, jax.random.key(1), g)
            (_, sg, _), = items
            assert sg.layout == "rice"

    def test_two_phase_exchange_multi_worker(self):
        """The cross-worker dimension of the two-phase exchange, on 8 fake
        devices (subprocess — the main pytest process stays
        single-device): every worker draws a DIFFERENT coordinate set, so
        the phase-one gathered counts genuinely differ per worker and the
        padding-zeroing / gcounts slicing runs off other workers' lengths.
        Rice must stay bit-identical to the coo gather of the same draw
        (layout invariance is exact at any m), stay within psum
        reduction-order tolerance of the dense wire, and report
        per-worker realized bytes that differ across workers and undercut
        forced coo."""
        from dist_harness import run_with_devices
        out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.api import CompressionConfig
from repro.comm.sync import sync_tree

rng = np.random.default_rng(1)
grads = {
    "w": jnp.asarray((rng.standard_normal((8, 4096))
                      * np.exp(rng.standard_normal((8, 4096))))
                     .astype(np.float32)),
    "stack": jnp.asarray(rng.standard_normal((8, 3, 2048)), jnp.float32),
}
STACKED = {"w": False, "stack": True}
mesh = jax.make_mesh((8,), ("data",))

def run(cfg, key):
    def step(k, g):
        g = jax.tree.map(lambda x: x[0], g)      # this worker's shard
        synced, _, stats = sync_tree(cfg, k, g, data_axis="data",
                                     stacked=STACKED)
        return synced, jnp.reshape(stats.wire_bytes, (1,))
    with jax.set_mesh(mesh):
        fn = jax.jit(jax.shard_map(step, mesh=mesh,
                                   in_specs=(P(), P("data")),
                                   out_specs=(P(), P("data")),
                                   axis_names={"data"}, check_vma=False))
        return fn(key, grads)

key = jax.random.key(3)
kw = dict(name="gspar", rho=0.05, min_leaf_size=64, backend="reference",
          capacity_slack=4.0)
dense, _ = run(CompressionConfig(wire="dense", **kw), key)
coo, wb_coo = run(CompressionConfig(wire="gather", wire_layout="coo",
                                    **kw), key)
rice, wb_rice = run(CompressionConfig(wire="gather", wire_layout="rice",
                                      **kw), key)
for a, b in zip(jax.tree.leaves(coo), jax.tree.leaves(rice)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(rice)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
wb_rice = np.asarray(wb_rice).reshape(-1)
wb_coo = np.asarray(wb_coo).reshape(-1)
assert np.unique(wb_rice).size > 1, wb_rice   # true per-worker lengths
assert np.all(wb_rice < wb_coo), (wb_rice, wb_coo)
print("per-worker rice bytes", wb_rice.tolist())
print("OK")
""")
        assert "OK" in out

    def test_two_phase_counts_are_decode_authoritative(self):
        """Zeroing words past the phase-one count must not change the
        decode (padding carries no protocol bits) — and corrupting a word
        INSIDE the counted region must. Pins that the exchange's counts
        describe exactly the meaningful payload."""
        rng = np.random.default_rng(13)
        d, k_cap = 1 << 12, 256
        _, vals, idx, _ = _sparse_leaf(rng, d, 150, k_cap)
        r = coding.rice_parameter(k_cap, d)
        sv, w, used = compaction.rice_encode(vals, idx, d, r)
        u = int(used)
        base = np.asarray(compaction.rice_decode(w, k_cap, d, r))
        w_np = np.asarray(w).copy()
        w_np[u:] = -1                      # garbage beyond the count
        masked = jnp.where(jnp.arange(w_np.shape[0]) < u, jnp.asarray(w_np),
                           0)             # what unpack_gathered does
        np.testing.assert_array_equal(
            np.asarray(compaction.rice_decode(masked, k_cap, d, r)), base)
        w_in = np.asarray(w).copy()
        w_in[max(0, u - 1)] ^= 1 << 7      # flip a counted bit
        assert not np.array_equal(
            np.asarray(compaction.rice_decode(jnp.asarray(w_in), k_cap, d,
                                              r)), base)


class TestRiceFitted:
    """Wire-format v4: the data-fitted Golomb-Rice parameter, shipped in
    the high bits of the phase-one counts word."""

    def test_fitted_encoder_matches_model_and_never_exceeds_static(self):
        """Property sweep across random draws: the fitted encoder's used
        count == the coding model's fitted prediction, the header's r ==
        the model's first-minimum pick over the window, and the fitted
        stream NEVER exceeds the static-parameter stream (r_s is in the
        window). 24 draws (vs the static sweep's 60): the fitted encoder
        packs every window candidate per draw, so the same wall clock buys
        fewer draws."""
        rng = np.random.default_rng(17)
        for _ in range(24):
            d = int(rng.integers(64, 1 << 16))
            k_cap = int(min(d, rng.integers(1, 1024)))
            n_live = int(rng.integers(0, k_cap + 1))
            _, vals, idx, _ = _sparse_leaf(rng, d, n_live, k_cap)
            window = coding.rice_fit_window(k_cap, d)
            _, w, header = compaction.rice_encode_fitted(vals, idx, d,
                                                         window)
            used = int(header) & compaction.RICE_HDR_USED_MASK
            r_sel = int(header) >> compaction.RICE_HDR_SHIFT
            live_idx = np.asarray(idx)[np.asarray(vals) != 0]
            assert used == coding.rice_fitted_stream_words(live_idx, k_cap,
                                                           d)
            assert r_sel == coding.rice_fitted_parameter(live_idx, k_cap, d)
            assert used == coding.rice_stream_words(live_idx, k_cap, d,
                                                    r_sel)
            assert used <= coding.rice_stream_words(live_idx, k_cap, d), \
                (d, k_cap, n_live)
            assert w.shape[0] == compaction.rice_fit_cap_words(k_cap, d,
                                                               window)

    def test_fitted_roundtrip_across_gap_regimes(self):
        """Exact reconstruction from the shipped header across the gap
        distributions the window was designed around: uniform draws
        (geometric-mean gaps), a clustered front block (gaps ~1, rewards
        small r), and one far coordinate (max-delta unary mass, rewards
        large r). The clustered draw must also strictly BEAT the static
        parameter — the fit has to pay for its window somewhere."""
        d, k_cap = 1 << 14, 256
        rng = np.random.default_rng(23)
        window = coding.rice_fit_window(k_cap, d)
        regimes = {
            "uniform": np.sort(rng.choice(d, 200, replace=False)),
            "clustered": np.arange(200, dtype=np.int64),
            "single_far": np.asarray([d - 1]),
        }
        for name, coords in regimes.items():
            q = np.zeros(d, np.float32)
            q[coords] = 1.0 + rng.random(coords.size).astype(np.float32)
            vals, idx, _ = compaction.compact(jnp.asarray(q), k_cap)
            sv, w, header = compaction.rice_encode_fitted(vals, idx, d,
                                                          window)
            dec = np.asarray(compaction.rice_decode_fitted(
                w, k_cap, d, window, header))
            svn = np.asarray(sv)
            rec = np.zeros(d, np.float32)
            rec[dec[svn != 0]] = svn[svn != 0]
            np.testing.assert_array_equal(rec, q, err_msg=name)
            used = int(header) & compaction.RICE_HDR_USED_MASK
            static = coding.rice_stream_words(coords, k_cap, d)
            assert used <= static, name
            if name == "clustered":
                assert used < static, (used, static)

    def test_header_is_decode_authoritative(self):
        """The receiver decodes at the header's r — not its own re-fit.
        Encode the same stream at every window candidate with the STATIC
        encoder, ship each under its own header, and the fitted decode
        must reproduce that candidate's decode exactly (even for the
        candidates the fit would not have picked)."""
        rng = np.random.default_rng(29)
        d, k_cap = 1 << 12, 128
        _, vals, idx, _ = _sparse_leaf(rng, d, 100, k_cap)
        window = coding.rice_fit_window(k_cap, d)
        assert len(window) > 1
        cap = compaction.rice_fit_cap_words(k_cap, d, window)
        for r in window:
            _, w, used = compaction.rice_encode(vals, idx, d, r)
            padded = jnp.zeros((cap,), jnp.int32).at[:w.shape[0]].set(w)
            header = jnp.int32((r << compaction.RICE_HDR_SHIFT)
                               | int(used))
            got = compaction.rice_decode_fitted(padded, k_cap, d, window,
                                                header)
            expect = compaction.rice_decode(w, k_cap, d, r)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(expect), err_msg=r)

    def test_zero_header_skip_sentinel_decodes_dead(self):
        """The skip sentinel: an all-zero message with a zeroed header
        must decode to zero-valued slots only — the receiver's zero-value
        masking drops the whole message."""
        d, k_cap = 4096, 64
        window = coding.rice_fit_window(k_cap, d)
        cap = compaction.rice_fit_cap_words(k_cap, d, window)
        idx = compaction.rice_decode_fitted(jnp.zeros((cap,), jnp.int32),
                                            k_cap, d, window,
                                            jnp.int32(0))
        assert idx.shape == (k_cap,)   # fixed shape; values gate liveness

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_fitted_on_the_wire_bit_identical_and_never_more_bytes(
            self, backend):
        """cfg.rice_fitted on the real collective: the synced tree stays
        bit-identical to the static-parameter rice wire (the fit changes
        only the index coding, never the selected coordinates), and the
        realized wire bytes never exceed the static run's."""
        grads = _grad_tree(8)
        key = jax.random.key(5)
        kw = dict(name="gspar", rho=0.05, min_leaf_size=64, backend=backend,
                  capacity_slack=4.0, wire="gather", wire_layout="rice")
        s_stat, _, st_stat = _sync(CompressionConfig(**kw), key, grads)
        s_fit, _, st_fit = _sync(CompressionConfig(rice_fitted=True, **kw),
                                 key, grads)
        for a, b in zip(jax.tree.leaves(s_stat), jax.tree.leaves(s_fit)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(st_fit.wire_bytes) <= float(st_stat.wire_bytes)
        assert float(st_fit.wire_bytes) > 0
