"""Every correctness limit, of the cells and of the test sizes, sits above
the highest reading of sound runs, and the control and each fault read
above the limit of at least one number (PERF.md, section 2)."""
import json

import pytest

from chipbench import compare, spec

import tiny

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CASES = [(w["name"], spec.load(w["name"], BENCH).cell)
         for w in BENCH["workloads"]]
CASES += [(n, json.loads((tiny.DATA / f"{n}.limits.json").read_text()))
          for n in ("tiny-sc2", "tiny-rwkv6", "tiny-dsv2")]


@pytest.mark.parametrize("name,entry", CASES, ids=[c[0] for c in CASES])
def test_limits_between_readings(name, entry):
    limits, readings = entry["limits"], entry["readings"]
    assert set(limits) == set(compare.NAMES)
    for k in compare.NAMES:
        assert readings["program_max"][k] < limits[k], k
    uppers = {"control": readings["control_min"], **readings["faults_min"]}
    for what, upper in uppers.items():
        assert any(upper[k] > limits[k] for k in upper), what
