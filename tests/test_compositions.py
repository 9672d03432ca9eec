"""Composable compression stack (selector ∘ codec) tests:

  * dense-wire vs gather-wire bit-identity for EVERY registered composition
    under the reference backend (the tentpole contract), incl. the legacy
    monoliths qsgd/terngrad that used to be banned from the sparse wires
  * gspar+qsgd8 and terngrad end-to-end on the gather wire of a real
    (4 data x 2 model) device mesh, bit-identical to the dense wire
  * closed-form (Algorithm 2) parity: gspar(algo="closed") through the
    compress_tree_sparse reference fallback vs the dense path, same key —
    the previously-untested fallback named in ROADMAP
  * coding-model property: realized bits never exceed the Theorem-4-style
    "every kept coordinate listed at full price" bound, and match
    hand-computed bits on a small fixed vector, for every composition
  * bucket chunking: oversized coordinate spaces split into capacity-bounded
    wire chunks at plan time (bit-identical to the unchunked exchange) instead
    of aborting at the int32 guard
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_harness import run_with_devices
from repro.comm import compaction
from repro.comm.sync import sync_tree
from repro.core import coding
from repro.core.api import CompressionConfig, compress_tree, compress_tree_sparse

COMPOSITIONS = ("gspar", "unisp", "topk", "qsgd", "terngrad", "none",
                "gspar+bf16", "gspar+qsgd8", "gspar+ternary", "unisp+qsgd4",
                "topk+ternary", "bernoulli+ternary", "identity+qsgd8")


def _grad_tree(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal(4096)
                         * np.exp(rng.standard_normal(4096)), jnp.float32),
        "stack": jnp.asarray(rng.standard_normal((3, 2048)), jnp.float32),
        "tiny": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }


STACKED = {"w": False, "stack": True, "tiny": False}


def _densify_items(items, treedef):
    """Per-leaf dense reconstructions from the GROUP-level item stream:
    slice each group's concatenated payload / stacked rows back to leaves
    via its members map. Leaves come back flattened (per layer for
    stacked) — callers reshape against the reference tree."""
    leaves = [None] * treedef.num_leaves
    for kind, p, members in items:
        if kind == "dense":
            off = 0
            for i, sz in members:
                leaves[i] = p[off:off + sz]
                off += sz
        else:
            dense = p.densify()                  # [rows, d]
            r0 = 0
            for i, rows in members:
                leaves[i] = dense[r0:r0 + rows].reshape(-1)
                r0 += rows
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Dense vs gather bit-identity per composition (the tentpole contract)
# ---------------------------------------------------------------------------

class TestCompositionWireEquivalence:
    @pytest.mark.parametrize("name", COMPOSITIONS)
    def test_dense_vs_gather_bit_identical(self, name):
        """Same key, reference backend: the gather wire's decoded
        reconstruction must equal the dense-wire Q bit-for-bit — including
        the quantizing codecs, whose decode must happen identically on
        both paths."""
        grads = _grad_tree(0)
        key = jax.random.key(3)
        kw = dict(rho=0.05, min_leaf_size=64, backend="reference",
                  capacity_slack=4.0)
        q, _, stats_d = compress_tree(
            CompressionConfig(name=name, wire="dense", **kw), key, grads,
            stacked=STACKED)
        items, _, treedef, stats_g = compress_tree_sparse(
            CompressionConfig(name=name, wire="gather", **kw), key, grads,
            stacked=STACKED)
        recon = _densify_items(items, treedef)
        for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(recon)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32).reshape(a.shape))
        # the accounting agrees across wires too
        assert float(stats_d.bits) == pytest.approx(float(stats_g.bits),
                                                    rel=1e-6)

    @pytest.mark.parametrize("name", ["qsgd", "terngrad"])
    def test_legacy_dense_quantizers_ride_sparse_wire(self, name):
        """qsgd/terngrad were DENSE_ONLY before the refactor; as
        identity∘qsgd / bernoulli∘ternary they get capacity d (no silent
        truncation possible) and integer wire buffers."""
        grads = {"w": _grad_tree(1)["w"]}
        cfg = CompressionConfig(name=name, wire="gather", min_leaf_size=8,
                                backend="reference")
        items, _, _, _ = compress_tree_sparse(cfg, jax.random.key(0), grads)
        (_, sg, _), = items
        assert sg.k_cap == grads["w"].size       # full capacity: zero bias
        assert int(jnp.sum(sg.overflow())) == 0
        assert sg.values.dtype in (jnp.int8, jnp.int16)

    def test_ternary_codec_lossless_after_bernoulli(self):
        """Composed TernGrad is TernGrad: every bernoulli-kept value
        amplifies to ±max|g| (up to the one amplification-rounding ulp of
        g/p), so the ternary codec's stochastic rounding keeps everything
        (p = |v|/scale = 1) and every decoded value is exactly ±scale."""
        g = {"w": _grad_tree(2)["w"]}
        cfg = CompressionConfig(name="terngrad", wire="gather",
                                min_leaf_size=8, backend="reference")
        items, _, _, _ = compress_tree_sparse(cfg, jax.random.key(5), g)
        (_, sg, _), = items
        dec = np.asarray(sg.decode_values())
        scale = np.asarray(sg.scale, np.float32)
        nz = dec[dec != 0]
        assert len(nz) > 0
        # nothing zeroed by the codec: every selected coordinate survived
        assert len(nz) == int(jnp.sum(sg.nnz))
        np.testing.assert_array_equal(np.abs(nz), np.full(nz.shape, scale))
        # and the scale is max|g| up to amplification roundoff
        np.testing.assert_allclose(scale, float(jnp.max(jnp.abs(g["w"]))),
                                   rtol=1e-6)


class TestPallasCodecPaths:
    """The fused backend's codec plumbing (non-EF): float codecs quantize
    inside the kernel pass (out_dtype), integer codecs encode on the
    compact buffer — wire dtypes, decode parity vs reference, and the
    shared bits model."""

    @pytest.mark.parametrize("codec,wdt", [("bf16", jnp.bfloat16),
                                           ("qsgd8", jnp.int16),
                                           ("ternary", jnp.int8)])
    def test_pallas_codec_wire_dtype_and_decode(self, codec, wdt):
        rng = np.random.default_rng(21)
        g = {"w": jnp.asarray(rng.standard_normal(1 << 16)
                              * np.exp(rng.standard_normal(1 << 16)),
                              jnp.float32)}
        key = jax.random.key(17)
        base = dict(name="gspar", codec=codec, rho=0.05, wire="gather",
                    min_leaf_size=8, capacity_slack=4.0)
        pal_items, _, _, pal_stats = compress_tree_sparse(
            CompressionConfig(**base, backend="pallas"), key, g)
        ref_items, _, _, ref_stats = compress_tree_sparse(
            CompressionConfig(**base, backend="reference"), key, g)
        (_, sg, _), = pal_items
        assert sg.values.dtype == wdt
        a = np.asarray(ref_items[0][1].densify())
        b = np.asarray(sg.densify())
        scale = float(np.asarray(sg.scale).reshape(()))
        if codec == "bf16":
            # selection uniforms are shared (same key, in-kernel cast):
            # support and values agree up to draw-at-threshold coords
            assert float(np.mean((a != 0) != (b != 0))) < 2e-2
            both = (a != 0) & (b != 0)
            np.testing.assert_allclose(a[both], b[both], rtol=2e-2,
                                       atol=1e-3)
        elif codec == "qsgd8":
            # the pallas path draws its codec uniforms on the compact
            # buffer (reference draws dense-layout), so stochastic level
            # rounding differs per coordinate — by at most one level step
            both = (a != 0) & (b != 0)
            step = scale / 255.0
            assert np.abs(a[both] - b[both]).max() <= step * 1.01
            # and every decoded value sits on the level grid
            lv = b[b != 0] / step
            np.testing.assert_allclose(lv, np.round(lv), atol=1e-3)
        else:                                     # ternary
            nz = b[b != 0]
            assert len(nz) > 0
            np.testing.assert_allclose(np.abs(nz), scale, rtol=1e-6)
            # independent codec draws: densities agree statistically
            assert np.mean(b != 0) == pytest.approx(np.mean(a != 0),
                                                    rel=0.25)
        # both backends charge the same coding model (same regime)
        assert float(pal_stats.bits) == pytest.approx(
            float(ref_stats.bits), rel=0.1)


# ---------------------------------------------------------------------------
# Multi-device: compositions on the gather wire of a real mesh
# ---------------------------------------------------------------------------

_DIST_COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro.models import transformer as tf
from repro.models.common import split_params
from repro.core.api import CompressionConfig
from repro.dist import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.optim.optimizers import sgd
from repro.train import step as step_lib

cfg = tf.ModelConfig(name="tiny", vocab=64, d_model=32, pattern=("attn_full",),
                     num_periods=2, num_heads=4, num_kv_heads=2, head_dim=8,
                     d_ff=64, remat="none", dtype=jnp.float32)
params_t = tf.init_model(jax.random.key(0), cfg)
params, axes = split_params(params_t)
batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 16), 0, 64)}
opt = sgd(0.05)
opt_state = opt.init(params)
"""


@pytest.mark.parametrize("scheme", ["gspar+qsgd8", "terngrad"])
def test_composition_trains_on_gather_wire_multidevice(scheme):
    """The acceptance bar: a quantized composition runs Algorithm 1
    end-to-end on a (4 data x 2 model) mesh's gather wire — int levels +
    scales through the bucketed all_gather — and stays bit-identical to
    the dense wire under the same key."""
    out = run_with_devices(_DIST_COMMON + f"""
mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
rules = dict(shd.DP_RULES)
steps = {{}}
for wire in ("dense", "gather"):
    comp = CompressionConfig(name="{scheme}", rho=0.25, wire=wire,
                             min_leaf_size=8, capacity_slack=4.0,
                             backend="reference")
    with jax.set_mesh(mesh):
        ts = jax.jit(step_lib.make_compressed_train_step(cfg, comp, opt,
                                                         mesh, rules))
        p, s = params, opt_state
        for i in range(3):
            p, s, m = ts(p, s, batch, jax.random.key(7 + i))
        steps[wire] = (p, m)
pd, pg = steps["dense"][0], steps["gather"][0]
mx = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), pd, pg)))
m = steps["gather"][1]
print("max param diff", mx, "density", float(m["density"]),
      "bits", float(m["bits"]), "wire_bytes", float(m["wire_bytes"]))
assert mx == 0.0, mx
assert float(m["bits"]) > 0 and float(m["wire_bytes"]) > 0
print("OK")
""")
    assert "OK" in out


def test_composition_ef_multidevice_exact():
    """gspar+qsgd8 with error feedback on the gather wire of a real mesh:
    params AND residual bit-identical to the dense wire across steps (the
    residual absorbs the qsgd level rounding identically on both wires)."""
    out = run_with_devices(_DIST_COMMON + """
from repro.train.step import init_compressed_feedback
mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
rules = dict(shd.DP_RULES)
out = {}
for wire in ("dense", "gather"):
    comp = CompressionConfig(name="gspar+qsgd8", rho=0.1, wire=wire,
                             min_leaf_size=8, error_feedback=True,
                             backend="reference", capacity_slack=4.0)
    ef = init_compressed_feedback(cfg, comp, mesh)
    with jax.set_mesh(mesh):
        ts = jax.jit(step_lib.make_compressed_train_step(cfg, comp, opt,
                                                         mesh, rules))
        p, s = params, opt_state
        for i in range(3):
            p, s, ef, m = ts(p, s, ef, batch, jax.random.key(7 + i))
    out[wire] = (p, ef)
mx = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))),
    out["dense"][0], out["gather"][0])))
mr = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))),
    out["dense"][1].residual, out["gather"][1].residual)))
rl1 = sum(float(jnp.sum(jnp.abs(r)))
          for r in jax.tree.leaves(out["gather"][1].residual))
print("param diff", mx, "residual diff", mr, "residual l1", rl1)
assert mx == 0.0 and mr == 0.0
assert rl1 > 0.0
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Closed-form (Algorithm 2) through the sparse reference fallback
# ---------------------------------------------------------------------------

class TestClosedFormSparseParity:
    @pytest.mark.parametrize("eps", [0.5, 1.0, 4.0])
    def test_closed_form_dense_vs_gather_bit_identical(self, eps):
        """gspar(algo="closed") has no fused kernel: the sparse wire runs
        it through the reference fallback. Same key => the compact buffers
        must reconstruct the dense-path Q bit-for-bit, plain and stacked
        leaves alike (the previously-untested fallback in ROADMAP)."""
        grads = _grad_tree(4)
        key = jax.random.key(11)
        kw = dict(algo="closed", eps=eps, rho=0.5, min_leaf_size=64,
                  backend="reference", capacity_slack=4.0)
        q, _, _ = compress_tree(
            CompressionConfig(name="gspar", wire="dense", **kw), key, grads,
            stacked=STACKED)
        items, _, treedef, _ = compress_tree_sparse(
            CompressionConfig(name="gspar", wire="gather", **kw), key,
            grads, stacked=STACKED)
        for (kind, payload, _) in items:
            if kind == "sparse":
                assert int(jnp.sum(payload.overflow())) == 0
        recon = _densify_items(items, treedef)
        for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(recon)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32).reshape(a.shape))

    def test_closed_form_pallas_backend_matches_reference(self):
        """backend='pallas' with algo='closed' runs the fused two-pass
        kernel with the closed-form lambda and must reconstruct the exact
        reference message: both paths derive the identical scalar from the
        identical sort (sparsify.closed_form_lambda) and the identical
        per-coordinate selection draws."""
        g = {"w": _grad_tree(5)["w"]}
        key = jax.random.key(13)
        kw = dict(name="gspar", algo="closed", eps=1.0, rho=0.5,
                  wire="gather", min_leaf_size=8, capacity_slack=4.0)
        ref_items, _, _, _ = compress_tree_sparse(
            CompressionConfig(**kw, backend="reference"), key, g)
        pal_items, _, _, _ = compress_tree_sparse(
            CompressionConfig(**kw, backend="pallas"), key, g)
        np.testing.assert_array_equal(
            np.asarray(ref_items[0][1].densify()),
            np.asarray(pal_items[0][1].densify()))


# ---------------------------------------------------------------------------
# Coding model: realized bits per composition
# ---------------------------------------------------------------------------

class TestCompositionCodingModel:
    @pytest.mark.parametrize("name", COMPOSITIONS)
    def test_realized_bits_within_listed_price_bound(self, name):
        """Theorem-4-style sanity: a realized message never costs more
        than every kept coordinate listed at full price — s(b + log2 d) +
        min(s log2 d, 2d) + b with s = realized nnz and b = the codec's
        value bits (the bound theorem4_bound_bits instantiates at rho=1)."""
        rng = np.random.default_rng(7)
        d = 2048
        g = jnp.asarray(rng.standard_normal(d)
                        * np.exp(1.5 * rng.standard_normal(d)), jnp.float32)
        cfg = CompressionConfig(name=name, rho=0.05, min_leaf_size=8)
        scheme = cfg.scheme()
        cg = scheme.compress(jax.random.key(2), g)
        nnz = int(jnp.sum(jnp.abs(cg.q) > 0))
        vb = scheme.codec.value_bits
        header = scheme.codec.header_bits
        bound = coding.theorem4_bound_bits(max(nnz, 1), 1.0, d,
                                           b=vb) + header
        assert float(cg.bits) <= bound * (1 + 1e-6), \
            (name, float(cg.bits), bound)

    def test_float_bits_is_accounting_only(self):
        """float_bits is the coding model's b, never a wire quantizer:
        float_bits=16 must change the charged bits but transmit the exact
        same values as float_bits=32 (only codec='bf16' actually rounds)."""
        g = _grad_tree(8)["w"]
        key = jax.random.key(19)
        q32 = CompressionConfig(name="gspar", rho=0.05,
                                float_bits=32).scheme().compress(key, g)
        q16 = CompressionConfig(name="gspar", rho=0.05,
                                float_bits=16).scheme().compress(key, g)
        np.testing.assert_array_equal(np.asarray(q32.q), np.asarray(q16.q))
        assert float(q16.bits) < float(q32.bits)
        qbf = CompressionConfig(name="gspar", codec="bf16",
                                rho=0.05).scheme().compress(key, g)
        assert float(jnp.max(jnp.abs(qbf.q - q32.q))) > 0.0

    def test_hand_computed_bits_small_vector(self):
        """Fixed d=8 vector, hand-evaluated coding model per composition:
        the implementation must reproduce the numbers exactly."""
        g = jnp.asarray([4.0, -2.0, 1.0, 0.0, 0.5, -0.25, 0.0, 8.0])
        d, logd = 8, 3.0
        key = jax.random.key(9)
        for name in COMPOSITIONS:
            cfg = CompressionConfig(name=name, rho=0.25, min_leaf_size=1)
            scheme = cfg.scheme()
            cg = scheme.compress(key, g)
            q = np.asarray(cg.q, np.float32)
            p = np.asarray(cg.p, np.float32).reshape(-1)
            nz = np.abs(q) > 0
            vb = scheme.codec.value_bits
            if scheme.codec.integer_coded:
                expect = min(nz.sum() * (vb + logd),
                             d * scheme.codec.dense_map_bits) \
                    + scheme.codec.header_bits
            elif scheme.selector.name in ("gspar", "bernoulli"):
                n_a = (nz & (p >= 1.0)).sum()
                n_b = (nz & (p < 1.0)).sum()
                expect = n_a * (vb + logd) + min(2.0 * d, n_b * logd) + vb
            elif scheme.selector.name == "unisp":
                expect = nz.sum() * (vb + logd) + vb
            elif scheme.selector.name == "topk":
                expect = max(1, round(cfg.rho * d)) * (vb + logd) + vb
            else:                                  # identity
                expect = d * vb
            assert float(cg.bits) == pytest.approx(float(expect),
                                                   rel=1e-6), name


# ---------------------------------------------------------------------------
# Shape-bucketed grouping: bit-identity vs the per-leaf formulation, and the
# O(groups) dispatch count
# ---------------------------------------------------------------------------

# duplicate AND unique shapes: "a"/"b" share the 4096 group, the stacked
# leaf's 2048-rows share a group with the flat "c", "tiny" rides the dense
# passthrough group
def _group_tree(seed):
    rng = np.random.default_rng(seed)
    t = {
        "a": jnp.asarray(rng.standard_normal(4096), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(4096), jnp.float32),
        "stack": jnp.asarray(rng.standard_normal((3, 2048)), jnp.float32),
        "c": jnp.asarray(rng.standard_normal(2048), jnp.float32),
        "tiny": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }
    stk = {"a": False, "b": False, "stack": True, "c": False, "tiny": False}
    return t, stk


class TestGroupedDispatch:
    """The shape-bucketed compression plan (repro.core.grouping): one
    vmapped emit per (dtype, d, k_cap) group must be BIT-identical to
    compressing every leaf separately with its own dispatch — same per-leaf
    PRNG keys, same per-row selector math — on both backends, with and
    without error feedback; and the grouped path must compile at most one
    emit computation per shape group."""

    def _per_leaf(self, cfg, key, grads, stacked):
        """The retired per-leaf formulation, reconstructed leaf by leaf:
        one backend dispatch per leaf under compress_tree_sparse's exact
        key discipline (per-leaf split, per-layer split when stacked)."""
        from repro.core.grouping import leaf_rows
        from repro.core.sparse import resolve_backend
        backend = resolve_backend(cfg.backend)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        stk = jax.tree_util.tree_flatten(stacked)[0]
        keys = jax.random.split(key, len(leaves))
        dense_out, res_out = [], []
        for leaf, k, s in zip(leaves, keys, stk):
            if leaf.size < cfg.min_leaf_size:
                dense_out.append(leaf.astype(jnp.float32).reshape(-1))
                res_out.append(jnp.zeros_like(leaf))
                continue
            rows, d = leaf_rows(tuple(leaf.shape), s)
            k_cap = cfg.capacity(d)
            lk = (jax.random.split(k, rows) if rows > 1 else k[None])
            if cfg.error_feedback:
                sg, res = jax.vmap(lambda kk, gg: backend.compress_sparse_ef(
                    cfg, kk, gg, k_cap))(lk, leaf.reshape(rows, d))
                res_out.append(res.reshape(leaf.shape))
            else:
                sg = jax.vmap(lambda kk, gg: backend.compress_sparse(
                    cfg, kk, gg, k_cap))(lk, leaf.reshape(rows, d))
            dense_out.append(sg.densify().reshape(-1))
        return dense_out, res_out, treedef

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    @pytest.mark.parametrize("ef", [False, True])
    def test_grouped_bit_identical_to_per_leaf(self, backend, ef):
        grads, stk = _group_tree(31)
        key = jax.random.key(23)
        cfg = CompressionConfig(name="gspar", rho=0.05, wire="gather",
                                min_leaf_size=64, capacity_slack=4.0,
                                backend=backend, error_feedback=ef)
        res0 = jax.tree.map(jnp.zeros_like, grads) if ef else None
        items, res_g, treedef, _ = compress_tree_sparse(
            cfg, key, grads, stacked=stk, residual=res0)
        recon = _densify_items(items, treedef)
        ref_dense, ref_res, _ = self._per_leaf(cfg, key, grads, stk)
        for a, b in zip(ref_dense, jax.tree.leaves(recon)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b).reshape(a.shape))
        if ef:
            for a, b in zip(ref_res, jax.tree.leaves(res_g)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plan_collapses_duplicate_shapes(self):
        from repro.core.grouping import plan_tree
        grads, stk = _group_tree(0)
        cfg = CompressionConfig(name="gspar", rho=0.05, wire="gather",
                                min_leaf_size=64, capacity_slack=4.0)
        leaves = jax.tree.leaves(grads)
        plan = plan_tree(cfg, leaves, jax.tree.leaves(stk))
        # 5 leaves -> 2 sparse groups (4096x2; 2048: 3 stacked rows + flat)
        # + 1 dense passthrough group
        assert plan.n_leaves == 5
        assert plan.dispatch_count == 2
        kinds = [g.kind for g in plan.groups]
        assert kinds.count("sparse") == 2 and kinds.count("dense") == 1
        rows = {(g.d, g.rows) for g in plan.groups if g.kind == "sparse"}
        assert rows == {(4096, 2), (2048, 4)}
        # cached: same config + same specs -> the identical plan object
        assert plan is plan_tree(cfg, leaves, jax.tree.leaves(stk))

    def test_trace_count_one_emit_per_group(self):
        """Compiled-HLO dispatch count: the reference backend's compaction
        costs exactly one sort (top_k) per EMIT COMPUTATION, so the whole
        5-leaf tree must compile exactly one sort per sparse shape group —
        the O(leaves) -> O(groups) claim on the artifact XLA actually
        runs."""
        from repro.core.grouping import plan_tree
        grads, stk = _group_tree(2)
        cfg = CompressionConfig(name="gspar", rho=0.05, wire="gather",
                                min_leaf_size=64, capacity_slack=4.0,
                                backend="reference")
        plan = plan_tree(cfg, jax.tree.leaves(grads), jax.tree.leaves(stk))
        assert plan.dispatch_count == 2          # < 4 sparse leaves

        def compress(key, g):
            items, _, _, _ = compress_tree_sparse(cfg, key, g, stacked=stk)
            return [(sg.values, sg.idx) for kind, sg, _ in items
                    if kind == "sparse"]

        hlo = (jax.jit(compress)
               .lower(jax.random.key(0), grads).compile().as_text())
        n = 0
        for ln in hlo.splitlines():
            if " sort(" in ln or ln.strip().startswith("sort("):
                n += 1
            elif 'custom_call_target="TopK"' in ln:
                n += 1
        assert n == plan.dispatch_count, hlo.count("sort")


# ---------------------------------------------------------------------------
# Bucket coordinate-space guard
# ---------------------------------------------------------------------------

class TestBucketGuard:
    def test_check_bucket_coords_raises_past_int32(self):
        compaction.check_bucket_coords(2**31 - 1, 4)      # at the limit: ok
        with pytest.raises(ValueError, match="[Cc]hunk"):
            compaction.check_bucket_coords(2**31, 4)

    def test_huge_tree_plans_chunks_and_traces(self):
        """Three 2^30-coordinate leaves: the concatenated bucket coordinate
        space is past int32, which used to abort the sparse wire at trace
        time — the plan now splits it into capacity-bounded chunks and the
        sync traces through (abstractly: no 4 GiB arrays are built)."""
        from jax.sharding import PartitionSpec as P

        from repro.core.grouping import plan_tree
        big_d = 2**30
        cfg = CompressionConfig(name="gspar", rho=1e-6, wire="gather",
                                min_leaf_size=8)
        specs = {f"w{i}": jax.ShapeDtypeStruct((big_d,), jnp.float32)
                 for i in range(3)}
        plan = plan_tree(cfg, jax.tree.leaves(specs), [False] * 3)
        assert plan.chunk_count == 3             # one row per int32 window

        mesh = jax.make_mesh((1,), ("data",))

        def sync(g):
            synced, _, stats = sync_tree(cfg, jax.random.key(0), g,
                                         data_axis="data")
            return stats.overflow

        with jax.set_mesh(mesh):
            out = jax.eval_shape(jax.shard_map(
                sync, mesh=mesh, in_specs=(P(),), out_specs=P(),
                axis_names={"data"}, check_vma=False), specs)
        assert out.shape == ()

    def test_chunked_exchange_bit_identical_and_same_bytes(self):
        """Forcing a small bucket_coord_cap chunks a real tree's bucket;
        the synced gradients and the wire-byte accounting must both stay
        exactly what the single-chunk exchange produces."""
        from jax.sharding import PartitionSpec as P

        from repro.core.grouping import plan_tree
        rng = np.random.default_rng(17)
        grads = {f"w{i}": jnp.asarray(rng.standard_normal(1024),
                                      jnp.float32) for i in range(6)}
        kw = dict(name="gspar", rho=0.05, wire="gather", min_leaf_size=8,
                  capacity_slack=4.0)
        mesh = jax.make_mesh((1,), ("data",))

        def run(cfg):
            def sync(g):
                return sync_tree(cfg, jax.random.key(5), g,
                                 data_axis="data")
            with jax.set_mesh(mesh):
                return jax.jit(jax.shard_map(
                    sync, mesh=mesh, in_specs=(P(),),
                    out_specs=(P(), P(), P()), axis_names={"data"},
                    check_vma=False))(grads)

        ref, _, ref_stats = run(CompressionConfig(**kw))
        capped = CompressionConfig(bucket_coord_cap=2048, **kw)
        plan = plan_tree(capped, jax.tree.leaves(grads), [False] * 6)
        assert plan.chunk_count == 3             # 6 rows of 1024, 2 per cap
        got, _, got_stats = run(capped)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(got_stats.wire_bytes) == float(ref_stats.wire_bytes)
