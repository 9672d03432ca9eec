"""Wire-format / backend / scheme benchmark for the sparse-wire pipeline.

Measures, on a realistic mixed leaf set (one 1M-coordinate matrix, one
scan-over-layers stack, a handful of tiny vectors):

  * wall-clock per step of the full compress -> exchange pipeline for every
    (backend x wire) combination, run end-to-end inside a single-device
    shard_map so the collectives lower and the bucketing cost is real;
  * the same pipeline for every registered selector∘codec composition
    (gspar+qsgd8, terngrad, ... ) on its preferred wires — bytes moved,
    coding-model bits, density;
  * wire bytes actually moved per step (SyncStats accounting), the coding-
    model message bits, and realized density;
  * per-composition wire-format-v2/v3 accounting, side by side: coding-
    model bits, realized layout bytes (the statically chosen COO / bitmap
    / index-elided dense / Rice-coded layout per leaf,
    `repro.comm.wire_layout` — true encoded lengths for RICE leaves, which
    must reproduce the measured SyncStats.wire_bytes exactly), and the
    REALIZED cost of forcing every sparse leaf onto the RICE branch (the
    former off-wire Golomb estimator column, now the realized bytes of the
    fourth layout: encoder word geometry + phase-one counts) — asserting
    that identity+qsgd8 and bernoulli+ternary ride the gather wire
    strictly below the dense psum's bytes (the old ROADMAP caveat) and
    that at least one composition ships entropy-coded indices as its
    argmin layout;
  * bit-consistency of the pallas backend (interpret mode on CPU) against
    the pure-jnp reference of the same fused pipeline on the pregenerated-
    uniforms path — asserted, not just reported.

``python -m benchmarks.bench_wire --json`` additionally writes the full
payload to ``BENCH_wire.json`` at the repo root (the CI perf artifact);
``--full`` switches from the dryrun-sized leaf set to the 1M-coordinate one.
"""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import save_json, timed_us

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the composition matrix the refactor unlocked: each entry is measured on
# the dense + gather wires with the reference backend. identity+qsgd8 and
# bernoulli+ternary are the wire-format-v2 acceptance pair: full-capacity
# (k_cap = d) compositions whose realized gather bytes must undercut the
# dense psum now that the index stream is elided for them.
COMPOSED_SCHEMES = ("gspar", "gspar+bf16", "gspar+qsgd8", "topk+ternary",
                    "terngrad", "qsgd", "identity+qsgd8", "bernoulli+ternary")

# full-capacity compositions that must beat the dense wire's bytes at
# matched density (asserted below, gated in CI by scripts/check_bench.py)
DENSE_BEATERS = ("identity+qsgd8", "bernoulli+ternary", "terngrad", "qsgd")


def _wire_v3_accounting(items):
    """Offline wire-format accounting for one composition's sparse items:
    realized layout bytes (what the bucketed collective ships under the
    stamped layouts — true encoded lengths + phase-one counts for RICE
    leaves, static stream sizes otherwise, incl. per-message scales), the
    REALIZED cost of forcing every sparse leaf onto the RICE branch (the
    entropy-coded column: since wire-format v3 this is the realized fourth
    layout, word geometry and counts included, not an idealized
    estimator), and the per-layout leaf census."""
    from repro.core import codecs as codecs_lib
    from repro.core import coding

    layout_bytes = 0.0
    entropy_bytes = 0.0
    layouts: dict = {}
    for kind, p, _ in items:
        if kind == "dense":                   # tiny leaves: f32 psum
            layout_bytes += p.size * 4
            entropy_bytes += p.size * 4
            continue
        layouts[p.layout] = layouts.get(p.layout, 0) + 1
        has_scale = codecs_lib.get(p.codec).has_scale
        vals = np.asarray(p.values)
        idxs = np.asarray(p.idx)
        if vals.ndim == 1:
            vals, idxs = vals[None], idxs[None]
        if p.layout != "rice":
            layout_bytes += p.realized_wire_bits() / 8
        for v, ix in zip(vals, idxs):         # per layer
            live = v != 0
            rice_bytes = (p.k_cap * v.dtype.itemsize             # values
                          + coding.rice_stream_words(ix[live], p.k_cap,
                                                     p.d) * 4   # payload
                          + 4)                                  # count word
            entropy_bytes += rice_bytes
            if p.layout == "rice":
                layout_bytes += rice_bytes
            if has_scale:
                layout_bytes += 4
                entropy_bytes += 4
    return layout_bytes, entropy_bytes, layouts


def _leaf_set(quick: bool):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    big = (1 << 18) if quick else (1 << 20)
    stack = (4, 1 << 14) if quick else (8, 1 << 16)
    grads = {
        "w_big": jnp.asarray(rng.standard_normal(big)
                             * np.exp(rng.standard_normal(big)), jnp.float32),
        "w_stack": jnp.asarray(rng.standard_normal(stack), jnp.float32),
        "norms": [jnp.asarray(rng.standard_normal(128), jnp.float32)
                  for _ in range(4)],
    }
    stacked = {"w_big": False, "w_stack": True, "norms": [False] * 4}
    return grads, stacked


def run(quick: bool = False, return_payload: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comm.sync import sync_tree
    from repro.core.api import CompressionConfig, compress_tree_sparse

    rows, payload = [], {}
    grads, stacked = _leaf_set(quick)
    dense_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(grads))
    mesh = jax.make_mesh((1,), ("data",))
    rho = 0.01

    for backend in ("reference", "pallas"):
        for wire in ("dense", "gather", "packed"):
            for ef in (False, True):
                cfg = CompressionConfig(name="gspar", rho=rho, wire=wire,
                                        min_leaf_size=256, backend=backend,
                                        error_feedback=ef)

                # EF rows run the same pipeline plus the residual carry —
                # measuring the cost of one extra params-sized read/write
                if ef:
                    def step(key, g, res):
                        return sync_tree(cfg, key, g, data_axis="data",
                                         feedback=res)
                    args = (jax.random.key(7), grads,
                            jax.tree.map(jnp.zeros_like, grads))
                else:
                    def step(key, g):
                        synced, _, stats = sync_tree(cfg, key, g,
                                                     data_axis="data")
                        return synced, stats
                    args = (jax.random.key(7), grads)

                specs = (P(),) * len(args)
                with jax.set_mesh(mesh):
                    fn = jax.jit(jax.shard_map(
                        step, mesh=mesh, in_specs=specs,
                        out_specs=(P(),) * (3 if ef else 2),
                        axis_names={"data"}, check_vma=False))
                    out = fn(*args)                    # compile + warm
                    stats = out[-1]
                    jax.block_until_ready(out[0])
                    us = timed_us(lambda: jax.block_until_ready(fn(*args)[0]),
                                  iters=2 if quick else 5)
                rec = {
                    "us_per_step": us,
                    "wire_bytes": float(stats.wire_bytes),
                    "dense_bytes": float(dense_bytes),
                    "bits": float(stats.bits),
                    "dense_bits": float(stats.dense_bits),
                    "density": float(stats.density),
                    "overflow": float(stats.overflow),
                }
                tag = f"{backend}:{wire}" + (":ef" if ef else "")
                payload[tag] = rec
                rows.append((f"wire:{tag}", us,
                             f"wire_bytes={rec['wire_bytes']:.3g}"
                             f"(dense={float(dense_bytes):.3g});"
                             f"bits={rec['bits']:.3g};"
                             f"density={rec['density']:.4f}"))

    # composed-scheme matrix: every selector∘codec composition on the
    # dense and gather wires (reference backend) — the bytes/bits shape of
    # the compression zoo after the composable-compression refactor.
    items_by_scheme: dict = {}       # reused by the v3 acceptance loop
    for scheme in COMPOSED_SCHEMES:
        for wire in ("dense", "gather"):
            cfg = CompressionConfig(name=scheme, rho=rho, wire=wire,
                                    min_leaf_size=256, backend="reference")

            def step(key, g):
                synced, _, stats = sync_tree(cfg, key, g, data_axis="data")
                return synced, stats
            with jax.set_mesh(mesh):
                fn = jax.jit(jax.shard_map(
                    step, mesh=mesh, in_specs=(P(), P()),
                    out_specs=(P(), P()), axis_names={"data"},
                    check_vma=False))
                out = fn(jax.random.key(7), grads)
                stats = out[-1]
                jax.block_until_ready(out[0])
                us = timed_us(lambda: jax.block_until_ready(
                    fn(jax.random.key(7), grads)[0]),
                    iters=2 if quick else 5)
            rec = {
                "us_per_step": us,
                "wire_bytes": float(stats.wire_bytes),
                "dense_bytes": float(dense_bytes),
                "bits": float(stats.bits),
                "dense_bits": float(stats.dense_bits),
                "density": float(stats.density),
                "overflow": float(stats.overflow),
            }
            if wire == "gather":
                # wire-format-v2/v3 columns, side by side with the coding
                # model: realized layout bytes + the realized forced-RICE
                # cost of the SAME message the measured sync just shipped —
                # sync_tree folds the worker index into the key, which on
                # this 1-device data axis is fold_in(key, 0).
                worker_key = jax.random.fold_in(jax.random.key(7), 0)
                items, _, _, _ = compress_tree_sparse(cfg, worker_key, grads)
                items_by_scheme[scheme] = items
                lb, eb, lay = _wire_v3_accounting(items)
                rec["layout_bytes"] = lb
                rec["entropy_bytes"] = eb
                rec["layouts"] = lay
                # realized accounting must reproduce the measured HLO
                # bytes exactly — RICE rows prove the wire ships true
                # encoded lengths, not estimates or padded capacities
                assert abs(lb - rec["wire_bytes"]) < 1e-6 * max(lb, 1.0), (
                    scheme, lb, rec["wire_bytes"])
            tag = f"scheme:{scheme}:{wire}"
            payload[tag] = rec
            extra = (f";layouts={'/'.join(sorted(rec['layouts']))};"
                     f"layout_bytes={rec['layout_bytes']:.3g};"
                     f"entropy_bytes={rec['entropy_bytes']:.3g}"
                     if wire == "gather" else "")
            rows.append((f"wire:{tag}", us,
                         f"wire_bytes={rec['wire_bytes']:.3g};"
                         f"bits={rec['bits']:.3g}"
                         f"(dense={rec['dense_bits']:.3g});"
                         f"density={rec['density']:.4f}" + extra))

    # the wire-format-v2 acceptance bar (also the ROADMAP caveat it
    # closed): full-capacity quantized compositions must move fewer
    # realized bytes on the gather wire than the dense psum of the same
    # tree — the index stream is elided, not just modeled away.
    for scheme in DENSE_BEATERS:
        got = payload[f"scheme:{scheme}:gather"]["wire_bytes"]
        assert got < dense_bytes, (
            f"{scheme}: realized gather bytes {got:.0f} >= dense psum "
            f"{dense_bytes:.0f} — the wire-layout index elision regressed")

    # the wire-format-v3 acceptance bar: at least one composition's argmin
    # layout census includes RICE — realized (not estimated) entropy-coded
    # index bytes on the measured collective — and those rows undercut
    # what the same messages would have paid under the pre-v3 static
    # argmin (min over COO/BITMAP/DENSE).
    from repro.core import coding as coding_lib
    rice_rows = [k for k, r in payload.items()
                 if isinstance(r, dict) and r.get("layouts", {}).get("rice")]
    assert rice_rows, "no composition realized the RICE layout as argmin"
    for key_ in rice_rows:
        rec = payload[key_]
        items = items_by_scheme[key_.split(":")[1]]  # same cfg/key/grads
        pre_v3 = sum(
            p.size * 4 if kind == "dense" else
            min(coding_lib.realized_wire_bits(lay, p.k_cap, p.d,
                                              p.values.dtype.itemsize * 8)
                for lay in ("coo", "bitmap", "dense")) / 8
            for kind, p, _ in items)
        assert rec["wire_bytes"] < pre_v3, (key_, rec["wire_bytes"], pre_v3)
        rec["pre_v3_bytes"] = pre_v3

    # adaptive column (PR-10 acceptance, gated by scripts/check_bench.py):
    # the adaptive control loop's realized single-step bytes vs the static
    # pipeline at MATCHED density budget — same rho ceiling, same k_cap
    # capacities, same key, forced rice layout on both. Step 0 with zero
    # control state transmits the full gradient (delta against last_sent=0,
    # bound priming, no skips), so the byte delta isolates what the
    # data-fitted Golomb parameter and the adaptive density controller
    # save on the identical message. On THIS leaf set the two rows tie
    # exactly: iid coordinate draws are the geometric-gap case the static
    # parameter is already optimal for, so the fit selects it and pays
    # nothing — the gate is <= (the fitted window can never lose; see
    # coding.rice_fit_window). The strict wins live where the draws are
    # not geometric: clustered index regimes (test_rice.py
    # TestRiceFitted) and the cumulative convergence-vs-bytes harness
    # (tests/test_adaptive.py, delta coding + skipping included).
    from repro.optim.optimizers import ControlState, FeedbackState
    ad_kw = dict(rho=rho, min_leaf_size=256, backend="reference",
                 wire="gather", wire_layout="rice")
    ad_cfgs = {
        "adaptive:static": CompressionConfig(name="gspar", **ad_kw),
        "adaptive:fitted": CompressionConfig(
            name="agspar", error_feedback=True, adaptive=True,
            delta_beta=1.0, skip_tau=0.7, bound_decay=0.9,
            rice_fitted=True, **ad_kw),
    }
    for tag, cfg in ad_cfgs.items():
        adaptive = cfg.adaptive

        def step(key, g):
            if adaptive:
                fb = FeedbackState(residual=jax.tree.map(jnp.zeros_like, g))
                ctl = ControlState(
                    last_sent=jax.tree.map(jnp.zeros_like, g),
                    last_avg=jax.tree.map(jnp.zeros_like, g),
                    bound=jax.tree.map(
                        lambda x: jnp.zeros((), jnp.float32), g),
                    step=jnp.zeros((), jnp.int32))
                synced, _, _, stats = sync_tree(cfg, key, g,
                                                data_axis="data",
                                                feedback=fb, control=ctl)
            else:
                synced, _, stats = sync_tree(cfg, key, g, data_axis="data")
            return synced, stats
        with jax.set_mesh(mesh):
            fn = jax.jit(jax.shard_map(
                step, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                axis_names={"data"}, check_vma=False))
            out = fn(jax.random.key(7), grads)
            stats = out[-1]
            jax.block_until_ready(out[0])
            us = timed_us(lambda: jax.block_until_ready(
                fn(jax.random.key(7), grads)[0]),
                iters=2 if quick else 5)
        payload[tag] = {
            "us_per_step": us,
            "wire_bytes": float(stats.wire_bytes),
            "dense_bytes": float(dense_bytes),
            "density": float(stats.density),
        }
        rows.append((f"wire:{tag}", us,
                     f"wire_bytes={payload[tag]['wire_bytes']:.3g};"
                     f"density={payload[tag]['density']:.4f}"))
    assert (payload["adaptive:fitted"]["wire_bytes"]
            <= payload["adaptive:static"]["wire_bytes"]), (
        "adaptive realized bytes exceed the static pipeline's at matched "
        "density", payload["adaptive:fitted"]["wire_bytes"],
        payload["adaptive:static"]["wire_bytes"])

    # solver calibration: expected density (sum of sampling probabilities,
    # SparseGrad.p_sum) vs realized nnz over the leaf set — a persistent gap
    # flags a miscalibrated lambda.
    cal_cfg = CompressionConfig(name="gspar", rho=rho, wire="gather",
                                min_leaf_size=256, backend="reference")
    items, _, _, _ = compress_tree_sparse(cal_cfg, jax.random.key(11), grads,
                                          stacked=stacked)
    sparse = [sg for kind, sg, _ in items if kind == "sparse"]
    total_d = sum(sg.d * max(1, sg.p_sum.size) for sg in sparse)
    exp_nnz = sum(float(jnp.sum(sg.p_sum)) for sg in sparse)
    real_nnz = sum(float(jnp.sum(sg.nnz)) for sg in sparse)
    payload["calibration"] = {"expected_density": exp_nnz / total_d,
                              "realized_density": real_nnz / total_d}
    rows.append(("wire:calibration", 0.0,
                 f"expected_density={exp_nnz / total_d:.5f};"
                 f"realized_density={real_nnz / total_d:.5f}"))

    # pallas(interpret) vs pure-jnp reference of the same fused pipeline,
    # pregenerated uniforms: must agree bit-for-bit.
    from repro.kernels.sparsify import ops, ref
    n = 128 * 512
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal(n) * np.exp(rng.standard_normal(n)),
                    jnp.float32)
    u = jax.random.uniform(jax.random.key(5), (n,), jnp.float32)
    q_kernel = ops.gspar_sparsify(g, u, rho=0.05, num_iters=2, interpret=True)
    # the pure-jnp reference of the identical pipeline, on the kernel's own
    # padded [R, C] layout so every reduction sees the same operand shape
    g2d, _, _, _ = ops._pad_2d(g)
    u2d, _, _, _ = ops._pad_2d(u)
    pad = g2d.size - n                       # pad slots count as active zeros

    def ref_tail(t):
        n_below, l1_below = ref.tail_stats_ref(g2d, t)
        return n_below - float(pad), l1_below

    l1, _, mx = ref.stats_ref(g2d)
    lam = ops.greedy_lambda(l1, mx, 0.05, n, 2, tail_fn=ref_tail)
    q_ref = ref.sparsify_ref(g2d, u2d, lam).reshape(-1)[:n]
    exact = bool(jnp.all(q_kernel == q_ref))
    max_diff = float(jnp.max(jnp.abs(q_kernel - q_ref)))
    assert exact, f"pallas/reference divergence: max |diff| = {max_diff}"
    payload["bit_consistency"] = {"exact": exact, "max_diff": max_diff}
    rows.append(("wire:bit_consistency", 0.0,
                 f"pallas_interpret_vs_reference_exact={exact}"))

    save_json("wire", payload)
    return (rows, payload) if return_payload else rows


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_wire.json at the repo root")
    ap.add_argument("--full", action="store_true",
                    help="1M-coordinate leaf set instead of dryrun-sized")
    args = ap.parse_args()
    bench_rows, bench_payload = run(quick=not args.full,
                                    return_payload=True)
    emit(bench_rows)
    if args.json:
        path = os.path.join(REPO_ROOT, "BENCH_wire.json")
        with open(path, "w") as f:
            json.dump(bench_payload, f, indent=2, default=float)
        print(f"wrote {path}")
