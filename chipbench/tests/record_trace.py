#!/usr/bin/env python3
"""Record a traced run of a cell on the chip and keep a small excerpt of
its trace as test data for ``trace.py`` and the per-layer readers.

    python3 chipbench/tests/record_trace.py --workload sc2-gspar-ef \
        --seed 11 --seconds 10 --out chipbench/tests/data/v5e-sc2-gspar-ef

Runs the cell once with ``--trace 1`` as ``run.py`` does, then writes
``<out>.pbtxt``, the excerpt as a text-format XSpace (the host annotations
and, of the device's ``XLA Ops`` events inside the window, the first
``--events`` and the longest event of every operation key), and
``<out>.json``, the compiled step's ``hlo_table`` rows for the kept events
and the reduction of the whole trace and of the excerpt. Not part of a
benchmark run."""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

HOST_NAMES = ("window", "feed", "dispatch", "wait")
MAX_STR = 1500


def _quote(text: str) -> str:
    out = []
    for ch in text:
        if ch in '"\\':
            out.append("\\" + ch)
        elif ch == "\n":
            out.append("\\n")
        elif 32 <= ord(ch) < 127:
            out.append(ch)
        else:
            out.extend(f"\\{b:03o}" for b in ch.encode())
    return '"' + "".join(out) + '"'


def _short(text: str) -> str:
    cut = text.find(", backend_config=")
    return (text[:cut] if cut > 0 else text)[:MAX_STR]


def excerpt(pd, table: dict, n_events: int) -> tuple[str, dict]:
    """The text-format excerpt of ``pd`` and the table rows it needs."""
    from chipbench import trace
    spans = trace.host_spans(pd)
    lo, hi = next((s, e) for n, s, e in spans if n == "window")
    t0 = int(lo) - 1000
    stat_ids: dict = {}
    lines = []

    def events_text(evs, names):
        ev_ids, body = {}, []
        for name, start, dur, stats in evs:
            eid = ev_ids.setdefault(name, len(ev_ids) + 1)
            st = []
            for k, v in stats:
                sid = stat_ids.setdefault(k, len(stat_ids) + 1)
                if isinstance(v, str):
                    st.append(f"stats {{ metadata_id: {sid} "
                              f"str_value: {_quote(_short(v))} }}")
                elif isinstance(v, float):
                    st.append(f"stats {{ metadata_id: {sid} "
                              f"double_value: {v!r} }}")
                elif isinstance(v, int):
                    st.append(f"stats {{ metadata_id: {sid} "
                              f"int64_value: {v} }}")
            body.append(f"    events {{ metadata_id: {eid} offset_ps: "
                        f"{round((start - t0) * 1000)} duration_ps: "
                        f"{round(dur * 1000)} " + " ".join(st) + " }")
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in ev_ids.items()]
        names.update(ev_ids)
        return body, meta

    device, kept = [], {}
    for plane in pd.planes:
        if not trace.DEVICE.match(plane.name):
            continue
        evs = [ev for line in plane.lines if line.name == trace.OPS_LINE
               for ev in line.events
               if ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo]
        evs.sort(key=lambda e: e.start_ns)
        longest: dict = {}
        for ev in evs:
            key = trace.classify(ev.name, trace._stats(ev), table)[0]
            if key not in longest or ev.duration_ns > \
                    longest[key].duration_ns:
                longest[key] = ev
        chosen = {id(e): e for e in evs[:n_events]}
        chosen.update({id(e): e for e in longest.values()})
        pick = sorted(chosen.values(), key=lambda e: e.start_ns)
        body, meta = events_text(
            [(e.name, e.start_ns, e.duration_ns, list(trace._stats(e).items()))
             for e in pick], kept)
        device.append((plane.name, body, meta))
        break                                   # one chip is enough
    host = [(n, s, e - s, []) for n, s, e in spans if n in HOST_NAMES]
    hbody, hmeta = events_text(host, {})
    for name, body, meta in device:
        lines += ["planes {", "  id: 1", f"  name: {_quote(name)}",
                  "  lines {", "    id: 1", f'    name: "{trace.OPS_LINE}"',
                  f"    timestamp_ns: {t0}"] + body + ["  }"] + meta
    smeta = [f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
             f"{_quote(n)} }} }}" for n, i in stat_ids.items()]
    lines += smeta + ["}", "planes {", "  id: 2", '  name: "/host:CPU"',
                      "  lines {", "    id: 1", '    name: "python"',
                      f"    timestamp_ns: {t0}"] + hbody + ["  }"] + hmeta \
        + ["}"]
    short = {trace.instruction(n) for n in kept}
    rows = {n: [sorted(table[n][0]), table[n][1]] for n in short
            if n in table}
    return "\n".join(lines) + "\n", rows


def outline(pd, per_line: int = 3) -> list[str]:
    """Planes, their lines and a few events of each, with their stats, as
    lines of text: what a reduction of this trace has to expect."""
    from chipbench import trace
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                st = {k: (v[:160] if isinstance(v, str) else v)
                      for k, v in trace._stats(ev).items()}
                out.append(f"    {ev.name!r} {ev.duration_ns} ns {st}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    from chipbench import run as run_lib, spec, trace
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    (ROOT / ".chipbench_runs").mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=ROOT / ".chipbench_runs") as keep:
        result, lines = run_lib.run(spec.load(args.workload), args.seed,
                                    args.seconds, 1, keep=keep)
        keep = pathlib.Path(keep)
        table = trace.hlo_table((keep / "step.hlo.txt").read_text())
        pd = trace.load(keep / "trace.xplane.pb")
        out.with_suffix(".outline.txt").write_text(
            "\n".join(outline(pd)) + "\n")
        whole = trace.reduce(pd, hlo=table)
        text, rows = excerpt(pd, table, args.events)
    part = trace.reduce(ProfileData.from_text_proto(text), hlo={
        n: (frozenset(ops), pallas) for n, (ops, pallas) in rows.items()})
    out.with_suffix(".pbtxt").write_text(
        f"# Excerpt of a traced run of {args.workload} (seed {args.seed}) "
        "on the chip, by chipbench/tests/record_trace.py\n" + text)

    def plain(red):
        return {**red, "ops": {k: {**v, "runs": sorted(v["runs"])}
                               for k, v in red["ops"].items()}}
    out.with_suffix(".json").write_text(json.dumps(
        {"workload": args.workload, "seed": args.seed,
         "device": result["device"], "metrics": result["metrics"],
         "hlo_rows": rows, "whole": plain(whole), "excerpt": plain(part)},
        indent=1))
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
