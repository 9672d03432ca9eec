"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --smoke \
        --steps 50 --compressor gspar --rho 0.05 --wire gather

With 256 or more devices the full config and the production mesh are
selected automatically. ``--chip`` selects the arch's one-chip config
(published widths; depth and vocabulary cut to one chip's share), and
``--smoke`` a reduced variant for the CPU, where
XLA_FLAGS=--xla_force_host_platform_device_count=N --mesh NxM fakes a
multi-device run. ``build`` returns the job exactly as ``main`` runs it, so
other drivers (``chip_smoke.py``) step the same compiled program.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint
from repro.configs import registry
from repro.core.api import CompressionConfig
from repro.data.synthetic import token_batch
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.models.common import split_params
from repro.optim.optimizers import adam, init_control, init_feedback, sgd
from repro.train import step as step_lib


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced CPU-sized variant")
    ap.add_argument("--chip", action="store_true",
                    help="the arch's one-chip config: published widths, "
                         "depth and vocabulary cut to one chip's share")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    ap.add_argument("--compressor", default="gspar",
                    help="selector[+codec] composition (gspar, unisp, topk, "
                         "bernoulli, identity; e.g. 'gspar+qsgd8') or a "
                         "legacy alias (qsgd, terngrad, none)")
    ap.add_argument("--codec", default=None,
                    choices=[None, "f32", "bf16", "qsgd4", "qsgd8",
                             "ternary"],
                    help="value codec for the kept coordinates (default: "
                         "from --compressor, else f32)")
    ap.add_argument("--qsgd-bits", type=int, default=4,
                    help="levels exponent for the legacy 'qsgd' alias")
    ap.add_argument("--rho", type=float, default=0.05)
    ap.add_argument("--wire", default="dense",
                    choices=["dense", "gather", "packed"])
    ap.add_argument("--wire-layout", default="auto",
                    choices=["auto", "coo", "bitmap", "dense", "rice"],
                    help="sparse-wire bucket layout per leaf (auto = min "
                         "realized bytes: COO index list, packed occupancy "
                         "bitmap, index-elided dense value run, or "
                         "Golomb-Rice delta-coded index stream shipped via "
                         "the two-phase exchange)")
    ap.add_argument("--exchange", default="sync",
                    choices=["sync", "overlap"],
                    help="sparse collective structure: end-of-step barrier "
                         "or overlapped per-bucket exchange (fused word "
                         "streams issued in reverse-backward order)")
    ap.add_argument("--overlap-bucket-bytes", type=int, default=1 << 20,
                    help="payload cap per overlapped bucket (smaller = "
                         "finer comm/compute pipelining)")
    ap.add_argument("--xla-preset", default="none",
                    choices=["none", "async", "latency_hiding", "overlap"],
                    help="XLA comm-tuning flag preset "
                         "(repro.comm.xla_flags), applied before backend "
                         "init so async collectives / the latency-hiding "
                         "scheduler realize the overlapped issue order")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry the per-worker compression residual "
                         "(memory: one params-sized buffer per worker)")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive compression control loop (compressed "
                         "mode, requires --error-feedback): per-step delta "
                         "transmission against the last-sent state, "
                         "LASG-style communication skipping, per-leaf EMA "
                         "energy bounds")
    ap.add_argument("--delta-beta", type=float, default=1.0,
                    help="fraction of the last-sent EMA subtracted before "
                         "compression (0 disables delta coding)")
    ap.add_argument("--skip-tau", type=float, default=0.0,
                    help="skip a leaf's exchange when its delta energy is "
                         "<= tau * EMA bound (0 disables skipping)")
    ap.add_argument("--bound-decay", type=float, default=0.9,
                    help="EMA decay of the per-leaf skip bound")
    ap.add_argument("--rice-fitted", action="store_true",
                    help="data-fitted Golomb-Rice parameter per leaf, "
                         "shipped in the counts-header word (rice layout)")
    ap.add_argument("--resparsify-pods", action="store_true",
                    help="re-sparsify the inter-pod stage (Alg.1 step 7) "
                         "on multi-pod meshes; with --error-feedback the "
                         "pod stage carries its own per-pod residual")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="compression backend (pallas = fused kernels)")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 4x2 => (data=4, model=2); default: all-data")
    ap.add_argument("--mode", default=None, choices=[None, "compressed", "fsdp"])
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed place. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    changed; otherwise the cache lives in ``.jax_cache/`` at the root of the
    checkout (a fixed path: the path is part of the cache key). Call from a
    program's entry point, before the first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


@dataclasses.dataclass
class Job:
    """One training run as ``main`` builds it: the model and compression
    configs, the mesh, the jitted step ``(*state, batch, key) -> (*state,
    metrics)`` with the state donated, and ``init_state`` making that state
    (params, optimizer, then the EF residual and control state when on)
    afresh from the fixed seed, placed where the step keeps it.
    ``batch_sharding`` places each token batch (None: left to jit)."""
    cfg: tf.ModelConfig
    comp: CompressionConfig
    mesh: jax.sharding.Mesh
    train_step: Callable
    init_state: Callable[[], tuple]
    batch: int
    seq: int
    batch_sharding: jax.sharding.Sharding | None = None

    def step_inputs(self, key: jax.Array):
        """The data loop's key schedule: ``(next key, batch, step key)``."""
        key, k_data, k_q = jax.random.split(key, 3)
        batch = token_batch(k_data, self.cfg.vocab, self.batch, self.seq)
        if self.batch_sharding is not None:
            batch = jax.device_put(batch, self.batch_sharding)
        return key, batch, k_q


def build(args: argparse.Namespace) -> Job:
    if args.xla_preset != "none":
        # before the first backend touch (jax.devices() below inits XLA)
        from repro.comm.xla_flags import apply as apply_xla_preset
        applied = apply_xla_preset(args.xla_preset)
        print(f"xla_preset={args.xla_preset}: {len(applied)} flag(s)")

    spec = registry.get(args.arch)
    if args.chip and spec.chip is None:
        raise SystemExit(f"{args.arch} has no one-chip config")
    cfg = (spec.smoke if args.smoke else spec.chip if args.chip
           else spec.model)
    n_dev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)] if len(shape) < 3
                         else ("pod", "data", "model"))
    elif not (args.smoke or args.chip) and n_dev >= 256:
        mesh = make_production_mesh(multi_pod=(n_dev >= 512))
    else:
        mesh = make_mesh((n_dev, 1), ("data", "model"))
    multi_pod = "pod" in mesh.axis_names
    mode = args.mode or spec.train_mode

    rules = dict(shd.DP_RULES if mode == "compressed" else shd.FSDP_RULES)
    rules.update(spec.rules_overrides)
    if multi_pod:
        rules = shd.with_pod(rules)

    print(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} mode={mode}")
    if args.chip:
        for cut, note in spec.reduced.items():
            print(f"reduced {cut}: {note}")

    opt = (adam(args.lr) if args.optimizer == "adam" else sgd(args.lr))
    comp = CompressionConfig(name=args.compressor, codec=args.codec,
                             qsgd_bits=args.qsgd_bits, rho=args.rho,
                             wire=args.wire, wire_layout=args.wire_layout,
                             backend=args.backend,
                             error_feedback=args.error_feedback,
                             resparsify_pods=args.resparsify_pods,
                             exchange=args.exchange,
                             overlap_bucket_bytes=args.overlap_bucket_bytes,
                             xla_preset=args.xla_preset,
                             adaptive=args.adaptive,
                             delta_beta=args.delta_beta,
                             skip_tau=args.skip_tau,
                             bound_decay=args.bound_decay,
                             rice_fitted=args.rice_fitted,
                             min_leaf_size=1024)
    print(f"compression: {comp.describe()}")
    if comp.adaptive and mode != "compressed":
        raise SystemExit("--adaptive requires the compressed train mode")
    workers = step_lib.mesh_workers(mesh, multi_pod)

    def init_state() -> tuple:
        params, _ = split_params(tf.init_model(jax.random.key(0), cfg))
        state = (params, opt.init(params))
        if comp.error_feedback:
            # compressed mode: stacked per-worker residual (plus the per-pod
            # one when the pod stage recompresses); fsdp: params-shaped
            if mode == "compressed":
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                num_pods = (sizes["pod"]
                            if multi_pod and comp.resparsify_pods else None)
                state += (init_feedback(params, workers, num_pods=num_pods),)
            else:
                state += (init_feedback(params),)
        if comp.adaptive:
            state += (init_control(params, workers),)
        if mode == "compressed":
            state = jax.device_put(state, step_lib.compressed_state_shardings(
                mesh, state, multi_pod))
        return state

    with jax.set_mesh(mesh):
        # Donate the whole state (the EF residual too, which the grouped
        # compression path consumes into fresh stacked buffers): the train
        # loop rebinds all of it every step, so XLA can reuse its HBM for
        # the step's outputs instead of holding both copies live.
        donate = tuple(range(2 + comp.error_feedback + comp.adaptive))
        if mode == "compressed":
            train_step = jax.jit(step_lib.make_compressed_train_step(
                cfg, comp, opt, mesh, rules, multi_pod=multi_pod),
                donate_argnums=donate)
        else:
            train_step = jax.jit(step_lib.make_fsdp_train_step(
                cfg, comp, opt, mesh, rules), donate_argnums=donate)
    batch_sharding = None
    if mode == "compressed":
        # the step's manual worker axes split the batch
        batch_sharding = NamedSharding(
            mesh, P(("pod", "data") if multi_pod else "data"))
    return Job(cfg=cfg, comp=comp, mesh=mesh, train_step=train_step,
               init_state=init_state, batch=args.batch, seq=args.seq,
               batch_sharding=batch_sharding)


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    job = build(args)
    state = job.init_state()
    n_params = sum(p.size for p in jax.tree.leaves(state[0]))
    print(f"params: {n_params / 1e6:.1f}M")
    with jax.set_mesh(job.mesh):
        key = jax.random.key(1)
        t0 = time.time()
        for step_i in range(args.steps):
            key, batch, k_q = job.step_inputs(key)
            *state, metrics = job.train_step(*state, batch, k_q)
            if step_i % args.log_every == 0 or step_i == args.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                msg = (f"step {step_i:>5} loss {m['loss']:.4f}")
                if "density" in m:
                    msg += (f" density {m['density']:.4f}"
                            f" var x{m['var_ratio']:.2f}"
                            f" msg_bits {m['bits']:.3g}"
                            f" (dense {m['dense_bits']:.3g})")
                if job.comp.adaptive:
                    msg += f" skipped {m.get('skipped', 0.0):.1f}"
                print(msg, flush=True)
        dt = time.time() - t0
        print(f"done: {args.steps} steps in {dt:.1f}s "
              f"({args.steps / dt:.2f} steps/s)")

    if args.checkpoint:
        names = ["params", "opt"]
        if job.comp.error_feedback:
            # the EF residual is training state: restarting without it
            # re-biases the first compressed step after restore
            names.append("ef")
        if job.comp.adaptive:
            # ditto the control state: dropping it resets delta coding to a
            # cold full send and re-primes the skip bounds
            names.append("ctl")
        checkpoint.save(args.checkpoint, dict(zip(names, state)),
                        extra={"arch": args.arch, "steps": args.steps,
                               "error_feedback": job.comp.error_feedback,
                               "adaptive": job.comp.adaptive})
        print(f"checkpoint -> {args.checkpoint}")
    return 0


if __name__ == "__main__":
    main()
