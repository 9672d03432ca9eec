"""The control — the reference with float8 matmul operands in the
program's place — and the planted faults come out not correct against the
float32 reference, at a size the CPU holds, under the test size's limits.
On the chip the same readings are made at the cell's own size by
``chipbench/calibrate.py``; the cells' committed limits are held to those
readings by ``test_limits.py`` (a reading at test size says nothing of a
limit set at the cell's size)."""
import pytest

from chipbench import compare, data, reference

import tiny


@pytest.fixture(scope="module", params=["tiny-sc2", "tiny-rwkv6",
                                                 "tiny-dsv2"])
def readings(request):
    cell = tiny.cell(request.param)
    conf, traffic = cell.config, cell.traffic
    b, s = traffic["batch_per_chip"], traffic["seq"]
    ref = reference.Reference(conf, traffic)
    out = {"control": [], "half_batch": [], "answer": []}
    variants = {"control": reference.Reference(conf, traffic, lowp=True),
                "half_batch": reference.Reference(conf, traffic,
                                                  fault="half_batch"),
                "answer": reference.Reference(conf, traffic, fault="answer")}
    for seed in (101, 102, 103):
        keys = data.streams(seed)
        feed = data.step_feed(conf["vocab_size"], b, s)
        rows = [feed(keys, i)[0]["tokens"] for i in range(3)]
        r = ref.run(keys["weights"], keys["reference"], rows)
        for name, v in variants.items():
            out[name].append(compare.numbers(
                v.run(keys["weights"], keys["step"], rows), r))
    return cell.cell["limits"], out


@pytest.mark.parametrize("name", ["control", "half_batch", "answer"])
def test_fails(readings, name):
    limits, out = readings
    for nums in out[name]:
        assert not compare.verdict(nums, limits), nums
