#!/usr/bin/env python3
"""Keep a small excerpt of a traced chip run, with the stage of each kept
event's instruction, as test data for ``chipbench/stages.py``.

    python3 chipbench/stages.py --workload sc2-gspar-ef --seed 11 \
        --out run.json --keep run
    python3 chipbench/tests/record_stages.py --keep run --steps 2 \
        --out chipbench/tests/data/v5e-sc2-stages

reads the raw trace and the compiled step's HLO text that ``--keep`` left
(``trace.xplane.pb``, or gzipped as ``trace.xplane.pb.gz``, and
``step.hlo.txt``) and writes ``<out>.pbtxt``, the excerpt as
``record_trace.py`` cuts it (the host annotations and, of the device's
``XLA Ops`` events inside the window, the first ``--events`` and the
longest of every operation key), and ``<out>.json``: the stage of every
kept instruction, and the stage split of the whole trace and of the
excerpt. Needs no chip."""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _split_json(red: dict, steps: int) -> dict:
    from chipbench import stages
    return {**red, "stages": {str(k): v for k, v in red["stages"].items()},
            **stages.per_step(red, steps)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keep", required=True)
    ap.add_argument("--steps", type=int, required=True,
                    help="steps in the traced window")
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    import record_trace
    from chipbench import stages, trace
    keep = pathlib.Path(args.keep)
    text = (keep / "step.hlo.txt").read_text()
    with tempfile.TemporaryDirectory() as tmp:
        raw = keep / "trace.xplane.pb"
        if not raw.exists():
            raw = pathlib.Path(tmp) / "trace.xplane.pb"
            with gzip.open(keep / "trace.xplane.pb.gz") as src, \
                    open(raw, "wb") as dst:
                shutil.copyfileobj(src, dst)
        pd = trace.load(raw)
        table = stages.hlo_stages(text)
        whole = stages.split(pd, table)
        part, _ = record_trace.excerpt(pd, trace.hlo_table(text), args.events)
    excerpt = ProfileData.from_text_proto(part)
    kept = {trace.instruction(ev.name) for plane in excerpt.planes
            if trace.DEVICE.match(plane.name) for line in plane.lines
            for ev in line.events}
    rows = {n: table.get(n) for n in sorted(kept)}
    out = pathlib.Path(args.out)
    out.with_suffix(".pbtxt").write_text(
        "# Excerpt of a traced run on the chip, by "
        "chipbench/tests/record_stages.py\n" + part)
    out.with_suffix(".json").write_text(json.dumps(
        {"stage_rows": rows, "steps": args.steps,
         "whole": _split_json(whole, args.steps),
         "excerpt": _split_json(stages.split(excerpt, rows), args.steps)},
        indent=1))
    print(json.dumps(_split_json(whole, args.steps)["stage_ms"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
