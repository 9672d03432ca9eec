#!/usr/bin/env python3
"""Bring-up check of the compressed training step on a TPU.

    python chip_smoke.py                # one chip: gemma-2b chip config
    python chip_smoke.py --four-chips   # 4x1 data mesh: gather vs dense psum

One process drives the main path end to end: ``repro.launch.train.build``
makes the job exactly as ``python -m repro.launch.train --arch gemma-2b
--chip --compressor gspar --wire gather --backend pallas --error-feedback``
would, and this script compiles and steps it. Weights are random from the
launcher's fixed seed. It prints, line by line: the config, compile
seconds, the device memory the compiled step needs, whether the emit
kernels are compiled into the step, the loss of each step, a rerun's
losses, ``peak_bytes_in_use``, the pallas-vs-reference agreement on one
real-width leaf, and the on-core PRNG density. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every check passed. It times no step: step time and tokens per
second on the chip come from the benchmark, ``chipbench/run.py``.

With ``--four-chips`` it runs only the data-parallel phase: the same step
on a 4x1 (data, model) mesh with the sparse ``gather`` wire beside the
dense ``psum`` wire, same seed, and compares losses, synced gradient norms
and parameters.

It exits non-zero, without that last line, when JAX finds no TPU, when
the repository's ``src/`` is not beside it, or when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "gemma-2b"
STEPS = 4
# one sequence of train_4k per chip (repro.configs.registry.SHAPES)
SEQ = 4096
KERNELS = ("select_stats_lam", "compact_emit_lam")


def _launcher_argv(wire: str, backend: str, mesh: str, batch: int) -> list:
    return ["--arch", ARCH, "--chip", "--compressor", "gspar",
            "--wire", wire, "--backend", backend, "--error-feedback",
            "--mesh", mesh, "--batch", str(batch), "--seq", str(SEQ),
            "--steps", str(STEPS)]


def _gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


class Check:
    """Collects failed checks; every check prints its own line."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str):
        print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def compile_job(train, argv: list):
    """Build the launcher's job, lower and compile its step for the first
    step's inputs. Returns (job, compiled, seconds)."""
    import jax
    job = train.build(train.parse_args(argv))
    state = job.init_state()
    key = jax.random.key(1)
    _, batch, k_q = job.step_inputs(key)
    with jax.set_mesh(job.mesh):
        t0 = time.perf_counter()
        compiled = job.train_step.lower(*state, batch, k_q).compile()
        secs = time.perf_counter() - t0
    del state
    return job, compiled, secs


def run_steps(job, compiled, n: int):
    """Fresh state from the seed, then ``n`` steps through the compiled
    program. Returns (losses, synced grad norms, final state)."""
    import jax
    state = job.init_state()
    key = jax.random.key(1)
    losses, norms = [], []
    with jax.set_mesh(job.mesh):
        for _ in range(n):
            key, batch, k_q = job.step_inputs(key)
            *state, metrics = compiled(*state, batch, k_q)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
    return losses, norms, state


def leaf_agreement(check, cfg_kw: dict, shape: tuple):
    """Pallas against reference on one real-width leaf, compared on the
    compact (values, idx) pair. Both draw the same selection uniforms; the
    kernel's lambda is a scalar from tile-order sums, the reference's p is
    per coordinate, so a coordinate whose draw lies within their
    probability difference of the threshold may be kept by one and not the
    other. Every disagreement must be such a coordinate."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.api import CompressionConfig
    from repro.core.sparse import PallasBackend, ReferenceBackend
    from repro.kernels.sparsify import ops
    cfg = CompressionConfig(**cfg_kw)
    d = int(np.prod(shape))
    k_cap = cfg.capacity(d)
    g = (jax.random.normal(jax.random.key(7), shape, jnp.float32)
         * jnp.exp(jax.random.normal(jax.random.key(8), shape, jnp.float32))
         ).astype(jnp.bfloat16)
    key = jax.random.key(9)
    pal = jax.jit(lambda k, g: PallasBackend(interpret=False).compress_sparse(
        cfg, k, g, k_cap))(key, g)
    ref = jax.jit(lambda k, g: ReferenceBackend().compress_sparse(
        cfg, k, g, k_cap))(key, g)
    sel = cfg.scheme().selector
    k_sel, _ = cfg.scheme().split_key(key)
    u = np.asarray(jax.random.uniform(k_sel, shape, jnp.float32)).reshape(-1)
    a = np.abs(np.asarray(g, np.float32)).reshape(-1)
    p_ref = np.asarray(jax.jit(sel.probabilities)(g)).reshape(-1)
    lam = float(ops.gspar_lambda(g.reshape(-1), rho=sel.rho,
                                 num_iters=sel.num_iters))
    p_pal = np.minimum(np.float32(lam) * a, 1.0)
    dp = float(np.abs(p_pal - p_ref).max())
    unsat = (p_ref < 1.0) & (a > 0)
    lam_ref = p_ref[unsat] / a[unsat]     # the reference's implied lambda

    n_pal = min(int(pal.nnz), k_cap)
    pi = np.asarray(pal.idx)[:n_pal]
    pv = np.asarray(pal.values, np.float32)[:n_pal]
    rv_all = np.asarray(ref.values, np.float32)
    live = rv_all != 0
    order = np.argsort(np.asarray(ref.idx)[live])
    ri = np.asarray(ref.idx)[live][order]
    rv = rv_all[live][order]
    only_pal = np.setdiff1d(pi, ri)
    only_ref = np.setdiff1d(ri, pi)
    differ = np.concatenate([only_pal, only_ref])
    common, ip, ir = np.intersect1d(pi, ri, return_indices=True)
    vdiff = int((pv[ip] != rv[ir]).sum())
    print(f"agree leaf {shape} d={d} k_cap={k_cap} nnz pallas={int(pal.nnz)} "
          f"reference={int(ref.nnz)} sorted_prefix="
          f"{bool((np.diff(pi) > 0).all())}", flush=True)
    print(f"agree idx differ={differ.size} (pallas only {only_pal.size}, "
          f"reference only {only_ref.size}) values differ at common "
          f"coords={vdiff} of {common.size}", flush=True)
    print(f"agree lambda pallas={lam!r} reference p/|g| in "
          f"[{float(lam_ref.min())!r}, {float(lam_ref.max())!r}] "
          f"max|p_pallas - p_reference|={dp!r}", flush=True)
    if differ.size:
        margin = np.abs(u[differ] - p_ref[differ])
        print(f"agree differing coords: max |u - p| = {float(margin.max())!r}",
              flush=True)
        check(bool((margin <= dp).all()),
              "every (values, idx) disagreement is a draw within the lambda "
              "difference of its threshold")
    check(int(pal.nnz) <= k_cap and int(ref.nnz) <= k_cap, "no overflow")
    if vdiff:
        rel = np.abs(pv[ip] - rv[ir]) / np.maximum(np.abs(rv[ir]), 1e-30)
        print(f"agree values max relative difference {float(rel.max())!r}",
              flush=True)
        # v = g / p: a p difference of dp moves v by dp / p relative, and
        # the two bf16 roundings of the wire value add up to one ulp (2^-7)
        bound = float((dp / np.maximum(p_ref[common], 1e-30)).max()) + 2**-7
        check(bool((rel <= bound).all()),
              "common-coordinate values agree within the lambda difference")


def prng_density(check):
    """On-core PRNG path (not on the train step): realized nnz against
    binomial bounds of sum(p), the check the CPU test suite cannot make
    (the interpret-mode emulator yields zero bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.sparsify import ops
    n, rho = 1 << 16, 0.05
    g = jax.random.normal(jax.random.key(26), (n,), jnp.float32)
    q = ops.gspar_sparsify_prng(g, jnp.int32(1234), rho=rho)
    lam = float(ops.gspar_lambda(g, rho=rho))
    p = np.minimum(lam * np.abs(np.asarray(g)), 1.0)
    nnz = int((np.asarray(q) != 0).sum())
    sd = float(np.sqrt((p * (1 - p)).sum()))
    print(f"prng density nnz={nnz} expected={p.sum():.1f} sd={sd:.1f}",
          flush=True)
    check(abs(nnz - p.sum()) < 6 * sd,
          "on-core PRNG density within 6 sd of sum(p)")


def one_chip(check, train):
    import jax
    import numpy as np
    job, compiled, secs = compile_job(
        train, _launcher_argv("gather", "pallas", "1x1", 1))
    cfg = job.cfg
    print(f"config {cfg.name}: d_model={cfg.d_model} heads={cfg.num_heads}x"
          f"{cfg.head_dim} kv_heads={cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"mlp={cfg.mlp_kind}/{cfg.act} layers={cfg.num_layers} "
          f"vocab={cfg.vocab} batch={job.batch} seq={job.seq}", flush=True)
    print(f"compile {secs:.2f} s", flush=True)
    ma = compiled.memory_analysis()
    print(f"memory_analysis args={_gib(ma.argument_size_in_bytes)} "
          f"temp={_gib(ma.temp_size_in_bytes)} "
          f"out={_gib(ma.output_size_in_bytes)} "
          f"aliased={_gib(ma.alias_size_in_bytes)}", flush=True)
    hlo = compiled.as_text()
    n_calls = hlo.count("tpu_custom_call")
    print(f"hlo tpu_custom_call={n_calls} "
          + " ".join(f"{k}={k in hlo}" for k in KERNELS), flush=True)
    check(n_calls > 0 and all(k in hlo for k in KERNELS),
          "the compiled step holds the compiled emit kernels")

    losses, _, state = run_steps(job, compiled, STEPS)
    for i, loss in enumerate(losses):
        print(f"step {i} loss {loss!r}", flush=True)
    check(all(np.isfinite(losses)), "losses finite")
    n_params = sum(x.size for x in jax.tree.leaves(state[0]))
    print(f"params {n_params / 1e6:.1f}M", flush=True)
    del state
    rerun, _, state = run_steps(job, compiled, STEPS)
    del state
    print(f"rerun losses {rerun}", flush=True)
    check(rerun == losses, "a rerun from the same seed gives the same losses")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"({_gib(stats.get('peak_bytes_in_use', 0))}) of "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)
    del compiled, job

    leaf_agreement(check, dict(name="gspar", rho=0.05, wire="gather",
                               error_feedback=True),
                   (cfg.d_model, cfg.d_ff))
    prng_density(check)


def four_chips(check, train):
    """The data-parallel step on a 4x1 mesh, sparse gather wire (pallas
    kernels) beside the dense psum wire, from the same seed and batches.
    The metrics' ``grad_norm`` is the norm of the synced gradient each
    worker applies."""
    import jax
    import numpy as np
    runs = {}
    for wire in ("gather", "dense"):
        job, compiled, secs = compile_job(
            train, _launcher_argv(wire, "pallas", "4x1", 4))
        print(f"{wire}: compile {secs:.2f} s", flush=True)
        losses, norms, state = run_steps(job, compiled, 2)
        for d in jax.devices():
            used = (d.memory_stats() or {}).get("bytes_in_use", 0)
            print(f"{wire}: device {d.id} bytes_in_use {used} ({_gib(used)})",
                  flush=True)
        for i, (loss, norm) in enumerate(zip(losses, norms)):
            print(f"{wire}: step {i} loss {loss!r} synced grad_norm {norm!r}",
                  flush=True)
        check(all(np.isfinite(losses + norms)), f"{wire}: losses finite")
        runs[wire] = (losses, norms, jax.tree.map(np.asarray, state[0]))
        del state, compiled, job
    (lg, ng, pg), (ld, nd, pd) = runs["gather"], runs["dense"]
    print(f"loss gather {lg} dense {ld}", flush=True)
    rel = [abs(a - b) / b for a, b in zip(ng, nd)]
    print(f"synced grad_norm gather {ng} dense {nd}: bit-identical "
          f"{ng == nd}, largest relative difference {max(rel)!r}", flush=True)
    diffs = [float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
             for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(pd))]
    same = sum(int(np.array_equal(a, b))
               for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(pd)))
    print(f"params after 2 steps: {same} of {len(diffs)} leaves bit-identical "
          f"between the wires; largest difference {max(diffs)!r}", flush=True)
    check(lg[0] == ld[0], "step-0 loss identical (same params, same batch)")
    # the wires select the same coordinates but for draws at the threshold,
    # where the kernel's scalar lambda and the dense path's per-coordinate
    # p round apart (~1e-4 of coordinates): 1e-2 holds that and still
    # catches a wrong reduction (a sum instead of a mean is 4x off)
    check(max(rel) < 1e-2, "synced gradient norms agree between the wires")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 data-parallel phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import train
    train.use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: need {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    check = Check()
    (four_chips if args.four_chips else one_chip)(check, train)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
