#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload sc2-gspar-ef --seed 7 --seconds 10 --trace 0

Set-up builds the program's compressed train step for the cell (as
``repro.launch.train.build`` does), makes weights, Adam state and the
error-feedback residual on the device from ``--seed``, compiles the step
(JAX's persistent cache under ``.jax_cache/`` in the checkout, so only the
first run of a checkout compiles) and drives that same compiled step
through its first three steps, which the reference follows. Then it steps
for ``--seconds``, one step in flight as a trainer keeps it, whole steps
only: the window runs from the first measured step's dispatch to the last
one's completion. With ``--trace 1`` that window is profiled and the
cell's per-layer metrics are read from the trace and the program's own
counters; otherwise its end-to-end metrics (host clock) are reported.

Once the window has closed and the peak memory is read, the program's
state is freed and the plain float32 reference (``reference.py``) runs the
same three steps from the same weights and rows; ``compare.py`` decides
``correct``. The last lines of standard error give each number compared
beside its limit; the last line of standard output is the result as one
JSON object. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

FIRST_STEPS = 3       # driven in set-up; the reference follows them
# libtpu would log under /tmp/tpu_logs, a fixed path shared between runs
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def _sorted_norms(paths: list):
    """jitted ``leaves -> per-leaf norms``: leaves in ``paths`` order (the
    parameter tree's flatten order), norms in sorted path order."""
    import jax
    import jax.numpy as jnp
    order = sorted(range(len(paths)), key=lambda i: paths[i])

    @jax.jit
    def norms(leaves):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            leaves[i].astype(jnp.float32)))) for i in order])
    return norms


def _load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def window(compiled, state, feed, first: int, seconds: float, annotate):
    """Whole steps from step ``first`` until ``seconds`` have passed, one
    step in flight. Returns (state, per-step metrics, steps, seconds)."""
    import jax
    ann = (jax.profiler.TraceAnnotation if annotate
           else lambda _name: contextlib.nullcontext())
    out, pending, i = [], None, first
    t0 = time.perf_counter()
    with ann("window"):
        while True:
            with ann("feed"):
                batch, key = feed(i)
            with ann("dispatch"):
                *state, metrics = compiled(*state, batch, key)
            out.append(metrics)
            i += 1
            if pending is not None:
                with ann("wait"):
                    jax.block_until_ready(pending)
            pending = metrics
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("wait"):
            jax.block_until_ready((state, pending))
    return state, out, i - first, time.perf_counter() - t0, t0


class Harness:
    """The program's compiled step for one cell, and the set-up that every
    seed shares: the feed, the norm readers, the compiled step itself."""

    def __init__(self, cell, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from chipbench import data, job as job_lib, reference, weights
        self.cell, self.devices = cell, devices
        conf, traffic = cell.config, cell.traffic
        self.job = job = job_lib.build(conf, traffic,
                                       tuple(cell.cell["mesh"]), devices)
        self.feed = jax.jit(
            data.step_feed(conf["vocab_size"], job.global_batch, job.seq),
            out_shardings=({"tokens": job.batch_sharding},
                           NamedSharding(job.mesh, P())))
        self.norms = norms = _sorted_norms(job.param_paths)
        self.shapes = shapes = reference.layout(conf)
        store = job_lib.DTYPES[conf["dtype"]]

        @jax.jit
        def change(params, wkey):
            p0 = weights.make(conf["init"], wkey, shapes, store)
            return norms([x.astype(jnp.float32) - p0[k].astype(jnp.float32)
                          for k, x in zip(job.param_paths,
                                          jax.tree.leaves(params))])
        self.change = change

        @jax.jit
        def first_grad(m, residual):
            # after one step: m = (1 - beta1) Q and residual = x - Q, so
            # this is x, the gradient the compressor was handed (worker 0)
            return norms([a / (1 - reference.BETA1) + r[0].astype(jnp.float32)
                          for a, r in zip(m, residual)])
        self.first_grad = first_grad
        params = jax.eval_shape(job.init, jax.random.key(0))[0]
        if sorted(job.param_paths) != sorted(shapes) or any(
                tuple(x.shape) != shapes[p] for p, x in
                zip(job.param_paths, jax.tree.leaves(params))):
            raise SystemExit("the program's parameter tree differs from the "
                             "reference's layout")
        self.compiled = None

    def first_steps(self, keys):
        """Fresh state from the seed's keys, compiled on first use, driven
        through ``FIRST_STEPS`` steps of the window's own call and feed.
        Returns (state, the program's readings, the token rows)."""
        import jax
        job = self.job
        with jax.set_mesh(job.mesh):
            state = job.init(keys["weights"])
            if self.compiled is None:
                batch, key = self.feed(keys, 0)
                self.compiled = job.step.lower(*state, batch, key).compile()
            losses, rows = [], []
            for i in range(FIRST_STEPS):
                batch, key = self.feed(keys, i)
                rows.append(batch["tokens"])
                *state, metrics = self.compiled(*state, batch, key)
                losses.append(metrics["loss"])
                if i == 0:
                    first = self.first_grad(
                        jax.tree.leaves(state[1]["m"]),
                        jax.tree.leaves(state[2].residual))
            prog = {"loss": [float(x) for x in losses],
                    "first_grad_norm": [float(x) for x in first],
                    "change_norm": [float(x) for x in self.change(
                        state[0], keys["weights"])]}
        return state, prog, rows

    def step_hbm_bytes(self) -> int:
        mem = self.compiled.memory_analysis()
        return (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    def peak_bytes(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def free(state) -> None:
    import jax
    for x in jax.tree.leaves(state):
        x.delete()


def run(cell, seed: int, seconds: float, trace: int,
        require_chip: bool = True, keep=None) -> tuple[dict, list]:
    """One run of ``cell`` (``spec.Cell``). Returns the result object and
    the lines comparing each number with its limit. With ``keep`` (a
    directory) a traced run leaves there its raw trace and the compiled
    step's HLO text."""
    import jax

    from chipbench import compare, data, flops, reference, spec
    from chipbench import trace as trace_lib
    from repro.launch.train import use_compile_cache

    devices = _devices(cell.chips, require_chip)
    if cell.chips != 1:
        raise SystemExit("the reference models one worker: a cell on "
                         f"{cell.chips} chips needs one of several")
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf, traffic = cell.config, cell.traffic
    h = Harness(cell, devices)
    keys = data.streams(seed)
    state, prog, rows = h.first_steps(keys)
    run_dir = ROOT / ".chipbench_runs" / cell.name
    if trace:
        shutil.rmtree(run_dir, ignore_errors=True)
        jax.profiler.start_trace(str(run_dir))
    with jax.set_mesh(h.job.mesh):
        state, per_step, steps, secs, t0 = window(
            h.compiled, state, lambda i: h.feed(keys, i), FIRST_STEPS,
            seconds, annotate=bool(trace))
    if trace:
        jax.profiler.stop_trace()
    setup_s = t0 - T_START

    peak = h.peak_bytes()
    host = [{k: float(v) for k, v in m.items()} for m in per_step]
    all_losses = prog["loss"] + [m["loss"] for m in host]
    failed = sum(not math.isfinite(x) for x in all_losses)
    tokens = steps * h.job.global_batch * h.job.seq
    hlo_text = h.compiled.as_text() if trace else None
    free(state)
    del state

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {}
    if trace:
        found = trace_lib.find(run_dir)
        if keep is not None:
            keep = pathlib.Path(keep)
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(found, keep / "trace.xplane.pb")
            (keep / "step.hlo.txt").write_text(hlo_text)
        red = trace_lib.reduce(trace_lib.load(found),
                               hlo=trace_lib.hlo_table(hlo_text))
        del hlo_text
        shutil.rmtree(run_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        min_leaf = traffic["compression"]["min_leaf_size"]
        rec = {"trace": red, "steps": steps, "tokens": tokens,
               "chips": len(devices), "peaks": spec.peaks(device["kind"]),
               "flops_per_token": flops.per_token(conf, h.job.seq),
               "compress_coords": sum(math.prod(s) for s in h.shapes.values()
                                      if math.prod(s) >= min_leaf),
               "per_step": host, "step_hbm_bytes": h.step_hbm_bytes()}
        metrics = {}
        for m in cell.per_layer:
            value = _load_reader(m["name"])(rec)
            if value is None:
                # left out of the line, as a reader's silence is; the cell
                # lists the metric, so its absence refuses the run
                print(f"MISSING per-layer metric {m['name']}: found nothing "
                      f"to read in {cell.name}'s trace ({trace_lib.outline(red)})",
                      file=sys.stderr, flush=True)
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": [[n, s / red["devices"]]
                           for n, s in trace_lib.top_ops(red["ops"])],
            "idle_gaps": red["idle_gaps"]}
    else:
        values = {"tokens_per_s": tokens / secs, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    del h

    ref = reference.Reference(conf, traffic).run(
        keys["weights"], keys["reference"], rows)
    nums = compare.numbers(prog, ref)
    limits = cell.cell["limits"]
    result = {"correct": compare.verdict(nums, limits) and not failed,
              "attempted": FIRST_STEPS + steps, "failed": failed,
              "metrics": metrics, "device": device, **out,
              "checks": compare.summary(nums, limits)}
    detail = [f"compared grad leaf {nums['grad_leaf']}, change leaf "
              f"{nums['change_leaf']}; "
              f"left out {nums['left_out']}; steps {FIRST_STEPS}+{steps} in "
              f"{secs!r} s"]
    return result, detail + compare.lines(nums, limits)


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import spec
    cell = spec.load(args.workload)
    result, lines = run(cell, args.seed, args.seconds, args.trace)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
