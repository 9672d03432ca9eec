"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read.

- the traced window: the host ``TraceAnnotation`` named ``window`` that
  the harness opens around the measured steps;
- device busy time: per device plane (``/device:TPU:<n>``), the union of
  the intervals of its ``XLA Ops`` events inside the window, averaged over
  the devices;
- device time per HLO operation: summed over the window per operation
  name (the instruction name, which a TPU trace gives as the start of
  the event's HLO text, without its ``.<n>`` suffix, and for a fusion the
  notable operations fused into it) with its opcode, category and every
  opcode it runs, those of the computations a fusion calls included;
  where the compiled program's HLO text is given, an event is looked up
  in it by its instruction name, else parsed from its own HLO text;
- the Pallas kernels: custom calls whose HLO text names a TPU custom call;
- the longest idle gaps inside the window, each named after the innermost
  host annotation open at its middle (``feed``, ``dispatch``, ``wait``).
"""
from __future__ import annotations

import bisect
import pathlib
import re

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:")
# `%name = shape opcode(operands), ...`: the opcode is the first lower-case
# word after a space that opens a parenthesis (a shape's layout has only
# upper-case `T(`, `S(` after `:` or `)`, and a tuple shape's own
# parenthesis follows no word)
_OPCODE = re.compile(r"=\s.*?\s([a-z][a-z0-9\-]*)\(")
# in a module's text: `%comp (params) -> shape {` opens a computation and
# `  [ROOT] %name = shape opcode(...)` is one of its instructions
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# a TPU trace names each op event by its HLO text, `%name = shape op(...)`
_EVENT_NAME = re.compile(r"^%?([\w.\-]+)\s*=\s")
# fused operations that name a fusion in ``ops``
NOTABLE = ("scatter", "gather", "sort", "custom-call", "dot", "convolution",
           "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")


def find(run_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(run_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {run_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def instruction(name: str) -> str:
    """The HLO instruction name of an op event, whether the trace names the
    event by it (``fusion.12``) or by its HLO text (``%fusion.12 = ...``,
    as a TPU trace does)."""
    m = _EVENT_NAME.match(name)
    return m.group(1) if m else name


def opcode(name: str, stats: dict) -> str:
    """The HLO opcode of one op event: parsed from its HLO text where the
    trace carries it, in a stat or as the event's name, else from the
    op's name."""
    for text in [stats.get(k) for k in ("long_name", "hlo_text", "tf_op")] \
            + [name]:
        if isinstance(text, str):
            m = _OPCODE.search(text)
            if m:
                return m.group(1)
    return base_name(name).split("_")[0]


def hlo_table(text: str) -> dict:
    """``{instruction name: (opcodes, pallas)}`` of a compiled module's HLO
    text: the instruction's own opcode plus, for a fusion, every opcode of
    the computations it calls (recursively), and whether it is a Pallas
    kernel (a TPU custom call)."""
    comps: dict = {}
    instrs: dict = {}
    cur = None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        op = _OPCODE.search(line)
        instrs[m.group(1)] = (op.group(1) if op else "",
                              _CALLS.findall(line) if op and op.group(1)
                              == "fusion" else [],
                              "tpu_custom_call" in line)
        cur.append(m.group(1))
    memo: dict = {}

    def comp_ops(name):
        if name not in memo:
            memo[name] = set()          # a cycle reads as empty
            memo[name] = set().union(*(inst_ops(i)
                                       for i in comps.get(name, ())))
        return memo[name]

    def inst_ops(name):
        op, calls, _ = instrs[name]
        return {op}.union(*(comp_ops(c) for c in calls))
    return {name: (frozenset(inst_ops(name)), pallas)
            for name, (_, _, pallas) in instrs.items()}


def base_name(name: str) -> str:
    """An instruction name without its ``.<n>`` and ``.clone`` suffixes."""
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def is_pallas(op: str, stats: dict, name: str = "") -> bool:
    text = " ".join([str(stats.get(k, "")) for k in ("long_name", "hlo_text")]
                    + [name])
    return op == "custom-call" and "tpu_custom_call" in text


def union(intervals, lo: float, hi: float) -> float:
    """Total length of the union of ``(start, end)`` intervals inside
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo: float, hi: float):
    """Idle ``(start, end)`` stretches of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def host_spans(pd) -> list:
    """``(name, start_ns, end_ns)`` of every event on the host planes."""
    out = []
    for plane in pd.planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def classify(name: str, stats: dict, table: dict) -> tuple:
    """``(key, opcode, opcodes run, pallas)`` of one op event: from the
    compiled module's ``table`` (``hlo_table``) where it holds the
    instruction, else from the event's own HLO text."""
    op = opcode(name, stats)
    short = instruction(name)
    if short in table:
        ran, pallas = table[short]
    else:
        ran, pallas = frozenset({op}), is_pallas(op, stats, name)
    key = base_name(short)
    inner = sorted(ran.intersection(NOTABLE) - {op})
    if inner:
        key += "[" + ",".join(inner) + "]"
    return key, op, ran, pallas


def reduce(pd, annotations=("feed", "dispatch", "wait"),
           hlo: dict | None = None) -> dict:
    """The trace's numbers: ``window_s``, ``busy_s`` (mean over devices),
    ``devices``, ``ops`` (per op key: opcode, the opcodes it runs,
    category, seconds, count, summed over devices), ``pallas_s``,
    ``idle_gaps`` (the 10 longest, as ``[name, seconds]``). ``hlo`` is the
    ``hlo_table`` of the traced program."""
    table = hlo or {}
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("the trace has no host annotation named 'window'")
    lo, hi = windows[0]
    named = sorted((s, e, n) for n, s, e in spans if n in annotations)
    starts = [s for s, _, _ in named]

    ops: dict = {}
    busy, gaps, pallas_ns, n_dev = [], [], 0.0, 0
    for plane in pd.planes:
        if not DEVICE.match(plane.name):
            continue
        n_dev += 1
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                inside = min(e, hi) - max(s, lo)
                if inside <= 0:
                    continue
                intervals.append((s, e))
                st = _stats(ev)
                key, op, ran, pallas = classify(ev.name, st, table)
                rec = ops.setdefault(key, {
                    "opcode": op, "runs": set(),
                    "category": str(st.get("hlo_category", "")),
                    "seconds": 0.0, "count": 0})
                rec["runs"].update(ran)
                rec["seconds"] += inside * 1e-9
                rec["count"] += 1
                if pallas:
                    pallas_ns += inside
        busy.append(union(intervals, lo, hi))
        for gs, ge in _gaps(intervals, lo, hi):
            mid = 0.5 * (gs + ge)
            label = "none"
            i = bisect.bisect_right(starts, mid)
            for s, e, n in reversed(named[:i]):
                if s <= mid <= e:
                    label = n
                    break
            gaps.append((label, (ge - gs) * 1e-9))
    if not n_dev:
        raise ValueError("the trace has no TPU device plane")
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / n_dev * 1e-9,
            "devices": n_dev, "ops": ops, "pallas_s": pallas_ns * 1e-9,
            "idle_gaps": [[n, s] for n, s in gaps[:10]]}


def outline(red: dict) -> str:
    """One line on what a reduction found, for a reader that found
    nothing: devices, window, busy time, Pallas time and the busiest ops
    with the opcodes they run."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1]["seconds"])[:8]
    return (f"devices {red['devices']}, window {red['window_s']!r} s, busy "
            f"{red['busy_s']!r} s, pallas {red['pallas_s']!r} s, ops "
            + "; ".join(f"{k} {sorted(v['runs'])} {v['seconds']!r} s"
                        for k, v in top))


def top_ops(ops: dict, n: int = 10) -> list:
    """The ``n`` operations that took most device time, ``[name, s]``."""
    ranked = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])
    return [[name, rec["seconds"]] for name, rec in ranked[:n]]
