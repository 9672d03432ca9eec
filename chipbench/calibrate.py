#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness comparison are set from.

    python3 chipbench/calibrate.py --workload sc2-gspar-ef \
        --seeds 1-12 --control-seeds 101-103 --out chiprun_out/calib.json

Not part of a benchmark run. In one process it builds the cell's program
once and, for every seed of ``--seeds``, drives the first three steps as a
run does and compares them with the float32 reference: the lower readings
(sound runs). For every seed of ``--control-seeds`` it puts in the
program's place the control (the reference with float8 matmul operands)
and the reference with each planted fault (``half_batch``: the loss over
half of each row; ``answer``: the largest leaf's synced gradient doubled)
and compares those with the reference: the upper readings. A state left
unchanged reads 1 on ``change_gap`` by construction and needs no run.
Writes every reading, the worst per number, and the seconds each part took.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

FAULTS = ("half_batch", "answer")


def _seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def calibrate(cell, seeds, control_seeds, devices, log=print) -> dict:
    import jax

    from chipbench import compare, data, reference
    from chipbench.run import FIRST_STEPS, Harness, free

    conf, traffic = cell.config, cell.traffic
    h = Harness(cell, devices)
    ref = reference.Reference(conf, traffic)
    out = {"cell": cell.name, "program": [], "control": [],
           "faults": {f: [] for f in FAULTS}, "seconds": {}}

    feed = h.feed

    def rows_of(keys):
        return [feed(keys, i)[0]["tokens"] for i in range(FIRST_STEPS)]

    for seed in seeds:
        keys = data.streams(seed)
        t = time.perf_counter()
        state, prog, rows = h.first_steps(keys)
        jax.block_until_ready(state)
        t_prog = time.perf_counter() - t
        free(state)
        t = time.perf_counter()
        r = ref.run(keys["weights"], keys["reference"], rows)
        t_ref = time.perf_counter() - t
        nums = compare.numbers(prog, r)
        out["program"].append({"seed": seed, **nums, "program": prog,
                               "reference": r, "program_s": t_prog,
                               "reference_s": t_ref})
        log(f"program seed {seed}: " + " ".join(
            f"{k}={nums[k]!r}" for k in compare.NAMES)
            + f" ({t_prog:.1f} s program, {t_ref:.1f} s reference)")
    del h

    variants = {"control": reference.Reference(conf, traffic, lowp=True)}
    variants.update({f: reference.Reference(conf, traffic, fault=f)
                     for f in FAULTS})
    for seed in control_seeds:
        keys = data.streams(seed)
        rows = rows_of(keys)
        r = ref.run(keys["weights"], keys["reference"], rows)
        for name, variant in variants.items():
            t = time.perf_counter()
            v = variant.run(keys["weights"], keys["step"], rows)
            nums = compare.numbers(v, r)
            entry = {"seed": seed, **nums, "variant": v, "reference": r,
                     "seconds": time.perf_counter() - t}
            (out["control"] if name == "control"
             else out["faults"][name]).append(entry)
            log(f"{name} seed {seed}: " + " ".join(
                f"{k}={nums[k]!r}" for k in compare.NAMES))

    def worst(rows, pick):
        return {k: pick(r[k] for r in rows) for k in compare.NAMES} \
            if rows else None
    out["lower"] = worst(out["program"], max)
    out["upper_control"] = worst(out["control"], min)
    out["upper_faults"] = {f: worst(v, min) for f, v in
                           out["faults"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import spec
    from chipbench.run import _devices
    from repro.launch.train import use_compile_cache

    cell = spec.load(args.workload)
    devices = _devices(cell.chips, require_chip=True)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    res = calibrate(cell, _seeds(args.seeds), _seeds(args.control_seeds),
                    devices, log=lambda s: print(s, flush=True))
    res["device"] = {"kind": devices[0].device_kind, "count": len(devices)}
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({k: res[k] for k in ("lower", "upper_control",
                                          "upper_faults")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
