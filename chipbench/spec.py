"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict         # configs/<config>.json
    traffic: dict        # traffic/<traffic>.json
    cell: dict           # workloads/<cell>.json: mesh, limits
    end_to_end: tuple    # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``bench``) with its
    configuration, traffic and cell files."""
    bench = bench if bench is not None else _load(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(ROOT / conf_entry["file"]),
        traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
        cell=_load(HERE / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _load(HERE / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"chipbench/peaks.json (have {sorted(table)})")
    return table[device_kind]
