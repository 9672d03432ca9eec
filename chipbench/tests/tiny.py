"""Cells at a size a CPU test run holds, for the tests."""
import json
import pathlib

from chipbench import spec

DATA = pathlib.Path(__file__).resolve().parent / "data"


def cell(config: str = "tiny-sc2") -> spec.Cell:
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    with open(DATA / f"{config}.json") as f:
        conf = json.load(f)
    with open(DATA / "tiny-traffic.json") as f:
        traffic = json.load(f)
    with open(DATA / f"{config}.limits.json") as f:
        limits = json.load(f)
    return spec.Cell(name=config, chips=1, config=conf, traffic=traffic,
                     cell={"mesh": [1, 1], "limits": limits["limits"]},
                     end_to_end=tuple(bench["end_to_end"]),
                     per_layer=tuple(bench["per_layer"]))
