"""Each per-layer reader on a record with known answers, and the readers'
silence where they find nothing to read."""
import importlib.util
import json
import pathlib

import pytest

from chipbench import spec, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def reader(name):
    path = spec.HERE / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def rec():
    from jax.profiler import ProfileData
    red = trace.reduce(ProfileData.from_text_proto(
        (DATA / "synthetic_trace.pbtxt").read_text()))
    return {"trace": red, "steps": 1, "tokens": 10, "chips": 1,
            "peaks": {"bf16_flops_per_s": 1e13, "hbm_bytes_per_s": 1e9},
            "flops_per_token": 1e6, "compress_coords": 250,
            "per_step": [{"wire_bytes": 100.0, "var_ratio": 12.0},
                         {"wire_bytes": 110.0, "var_ratio": 14.0}],
            "step_hbm_bytes": 12345}


def test_every_per_layer_metric_has_a_reader():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(reader(m["name"]))


def test_values(rec):
    assert reader("device_idle_share")(rec) == pytest.approx(70.0)
    # 10 tokens x 1e6 FLOP in 10 us = 1e12 FLOP/s of a 1e13 peak
    assert reader("step_mfu")(rec) == pytest.approx(10.0)
    # 250 coordinates x 2 B at 1e9 B/s = 0.5 us against 1 us of Pallas
    assert reader("compress_kernel_roofline")(rec) == pytest.approx(50.0)
    # scatter 2 us + sort 0.5 us in one step
    assert reader("scatter_gather_sort_ms")(rec) == pytest.approx(2.5e-3)
    assert reader("wire_bytes_per_step")(rec) == pytest.approx(105.0)
    assert reader("var_ratio")(rec) == pytest.approx(13.0)
    assert reader("step_hbm_bytes")(rec) == 12345


def test_fused_scatter_counts(rec):
    rec["trace"]["ops"] = {
        "fusion[gather]": {"opcode": "fusion", "runs": {"fusion", "gather"},
                           "seconds": 3e-6},
        "fusion": {"opcode": "fusion", "runs": {"fusion", "add"},
                   "seconds": 5e-6}}
    assert reader("scatter_gather_sort_ms")(rec) == pytest.approx(3e-3)


def test_silent_when_nothing_to_read(rec):
    rec["trace"]["pallas_s"] = 0.0
    rec["trace"]["ops"] = {}
    assert reader("compress_kernel_roofline")(rec) is None
    assert reader("scatter_gather_sort_ms")(rec) is None
    rec["per_step"] = []
    assert reader("wire_bytes_per_step")(rec) is None
    assert reader("var_ratio")(rec) is None
