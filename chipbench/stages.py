#!/usr/bin/env python3
"""Device time per stage of the compressed train step.

The program runs each stage of its step under
``jax.named_scope("stage.<name>")`` (``repro.core.stages``), so every
instruction of the compiled step carries the stage in the ``op_name`` of
its HLO metadata; nested scopes read as ``.../stage.compress/.../
stage.compact/...`` and the innermost one names the instruction. A TPU
trace names each device event by its instruction (``trace.instruction``),
so each event's device time inside the traced window falls under a stage:
its own, or for an instruction the compiler made without metadata one
read off its neighbours (``hlo_stages``), or ``None``.

    python3 chipbench/stages.py --workload sc2-gspar-ef --seed 11 \
        --seconds 10 --out stages.json

runs the cell once with ``--trace 1`` as ``run.py`` does and writes to
``--out``: device milliseconds per step and chip of each stage, their sum
beside the busy time, the share of device time that no stage covers, and
the run's result line. ``--keep <dir>`` leaves the raw trace and the
compiled step's HLO text there. Not part of a benchmark run: ``run.py``
reads no stage yet."""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import trace  # noqa: E402

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s"
                          r"([a-z][a-z0-9\-]*)\(")
_FUSION_CALLS = re.compile(r"\sfusion\(.*\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_PARAMETER = re.compile(r"\sparameter\((\d+)\)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_STAGE = re.compile(r"stage\.(\w+)")


def stage_of(op_name: str) -> str | None:
    """The innermost ``stage.<name>`` of an ``op_name``, or None."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def _operands(line: str, start: int) -> list:
    """Instruction names in the operand list that opens at ``start``."""
    depth = 0
    for i in range(start, len(line)):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            return _OPERAND.findall(line[start:i])
    return _OPERAND.findall(line[start:])


def hlo_stages(text: str) -> dict:
    """``{instruction name: stage or None}`` of every instruction in a
    compiled module's HLO text: the innermost stage of its own ``op_name``,
    and for a fusion without one the stage of the root of the computation
    it fuses (through fusions nested in fusions).

    The compiler drops the metadata of some instructions it makes: the
    sort and the sorted scatter it lowers a large scatter to, copies,
    fusions wrapped in fusions. Such an instruction takes the stage most
    of the instructions that make its operands take (a parameter of a
    fused computation is made by the fusion's operand; a constant, which
    the compiler shares across the program, makes nothing), else the
    stage most of the instructions that read its result take, else, for a
    fusion, the stage most of its fused instructions carry. A tie leaves
    it unscoped."""
    own, opcode, calls, roots, members = {}, {}, {}, {}, {}
    operands: dict = {}               # name -> operand names
    readers: dict = {}                # name -> names reading it
    params: dict = {}                 # (computation, index) -> parameter
    comp = None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = stage_of(op.group(1)) if op else None
        opcode[name] = m.group(3)
        operands[name] = _operands(line, m.end() - 1)
        for operand in operands[name]:
            readers.setdefault(operand, []).append(name)
        fused = _FUSION_CALLS.search(line)
        if fused:
            calls[name] = fused.group(1)
        index = _PARAMETER.search(line)
        if index and comp is not None:
            params[comp, int(index.group(1))] = name
        if comp is not None:
            members.setdefault(comp, []).append(name)
            if m.group(1):
                roots[comp] = name
    # a fused computation's parameter k is made by operand k of its fusion
    for name, comp in calls.items():
        for k, operand in enumerate(operands[name]):
            if (comp, k) in params:
                operands[params[comp, k]] = [operand]

    def majority(stages):
        """The stage most of ``stages`` name; None where none or a tie."""
        counts = sorted((stages.count(s), s) for s in set(stages) - {None})
        if not counts or len(counts) > 1 and counts[-1][0] == counts[-2][0]:
            return None
        return counts[-1][1]

    def root_stage(name):
        found = own[name]
        while found is None and name in calls:
            name = roots.get(calls[name])
            if name is None:
                break
            found = own[name]
        return found

    made_by = {n: [o for o in ops if opcode.get(o, "constant") != "constant"]
               for n, ops in operands.items()}
    # both kinds of edge run one way (operands are made before they are
    # read, a fusion's operands before its fused parameters), so one pass
    # in dependency order settles each
    made = {}
    for name in _ordered(own, made_by):
        made[name] = root_stage(name) or majority(
            [made.get(o) for o in made_by[name]])
    read = {}
    for name in _ordered(own, readers):
        read[name] = made[name] or majority(
            [read.get(r) for r in readers.get(name, ())])
    return {name: read[name] or (majority(
                [own[i] for i in members.get(calls[name], ())
                 if opcode[i] != "constant"]) if name in calls else None)
            for name in own}


def _ordered(nodes, deps: dict) -> list:
    """``nodes`` ordered so that each follows every node of ``deps[node]``
    (an edge back to a node still open is left out)."""
    order, seen = [], set()
    for start in nodes:
        if start in seen:
            continue
        seen.add(start)
        stack = [(start, iter(deps.get(start, ())))]
        while stack:
            node, rest = stack[-1]
            for dep in rest:
                if dep in nodes and dep not in seen:
                    seen.add(dep)
                    stack.append((dep, iter(deps.get(dep, ()))))
                    break
            else:
                stack.pop()
                order.append(node)
    return order


def split(pd, stages: dict) -> dict:
    """Device seconds per stage inside the traced window, summed over the
    devices' ``XLA Ops`` events (an event counts the part of it inside the
    window): ``{"window_s", "busy_s" (mean over devices), "devices",
    "stages": {stage or None: seconds}}``. An event whose instruction is
    not in ``stages`` counts under None."""
    spans = trace.host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("the trace has no host annotation named 'window'")
    lo, hi = windows[0]
    secs: dict = {}
    busy, n_dev = [], 0
    for plane in pd.planes:
        if not trace.DEVICE.match(plane.name):
            continue
        n_dev += 1
        intervals = []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                inside = min(e, hi) - max(s, lo)
                if inside <= 0:
                    continue
                intervals.append((s, e))
                name = stages.get(trace.instruction(ev.name))
                secs[name] = secs.get(name, 0.0) + inside * 1e-9
        busy.append(trace.union(intervals, lo, hi))
    if not n_dev:
        raise ValueError("the trace has no TPU device plane")
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / n_dev * 1e-9,
            "devices": n_dev, "stages": secs}


def per_step(red: dict, steps: int) -> dict:
    """Device ms per step and chip of each stage that has time (None as
    ``"unscoped"``), their sum, busy ms per step and chip, and the share
    (%) of device time whose instruction carries no stage."""
    scale = 1000.0 / red["devices"] / steps
    ms = {("unscoped" if k is None else k): v * scale
          for k, v in red["stages"].items() if v > 0}
    total = sum(red["stages"].values())
    return {"stage_ms": ms, "sum_ms": sum(ms.values()),
            "busy_ms": red["busy_s"] * 1000.0 / steps,
            "unscoped_share": (100.0 * red["stages"].get(None, 0.0) / total
                               if total else None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep", help="directory to leave the raw trace and "
                    "the compiled step's HLO text in")
    args = ap.parse_args(argv)

    from chipbench import run as run_lib, spec
    (ROOT / ".chipbench_runs").mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=ROOT / ".chipbench_runs") as tmp:
        keep = pathlib.Path(args.keep or tmp)
        result, lines = run_lib.run(spec.load(args.workload), args.seed,
                                    args.seconds, 1, keep=keep)
        red = split(trace.load(keep / "trace.xplane.pb"),
                    hlo_stages((keep / "step.hlo.txt").read_text()))
    found = per_step(red, result["attempted"] - run_lib.FIRST_STEPS)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"workload": args.workload, "seed": args.seed, **found,
         "window_s": red["window_s"], "result": result}, indent=1))
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
