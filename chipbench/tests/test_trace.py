"""The trace reduction on a hand-made trace with known answers."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(
        (DATA / "synthetic_trace.pbtxt").read_text()))


def test_window_and_busy(synthetic):
    assert synthetic["devices"] == 1
    assert synthetic["window_s"] == pytest.approx(10e-6)
    # scatter [1, 3] us and the overlapping pallas [6, 7] + sort [6.5, 7];
    # the fusion at 21 us lies outside the window
    assert synthetic["busy_s"] == pytest.approx(3e-6)


def test_ops_by_opcode(synthetic):
    ops = synthetic["ops"]
    assert set(ops) == {"scatter", "custom-call", "sort"}
    assert ops["scatter"]["opcode"] == "scatter"
    assert ops["scatter"]["category"] == "data formatting"
    assert ops["scatter"]["seconds"] == pytest.approx(2e-6)
    assert ops["sort"]["opcode"] == "sort"          # tuple-shaped result
    assert ops["custom-call"]["opcode"] == "custom-call"
    assert synthetic["pallas_s"] == pytest.approx(1e-6)


def test_idle_gaps_named_by_host_annotation(synthetic):
    gaps = synthetic["idle_gaps"]
    # [0.5, 1] none, [3, 6] around the wait (middle 4.5 us), [7, 10.5]
    # around the feed (middle 8.75 us)
    assert [g[0] for g in gaps] == ["feed", "wait", "none"]
    assert [g[1] for g in gaps] == pytest.approx([3.5e-6, 3e-6, 0.5e-6])


def test_union():
    assert trace.union([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert trace.union([(0, 2), (1, 3), (5, 6)], 2.5, 5.5) == 1
    assert trace.union([], 0, 1) == 0


def test_opcode_falls_back_to_the_name():
    assert trace.opcode("gather.17", {}) == "gather"
    assert trace.opcode("custom-call.3", {"long_name": "no hlo here"}) \
        == "custom-call"


def _compiled_text():
    import jax
    import jax.numpy as jnp

    def f(x, i, u):
        y = x.at[i].add(u)                 # a scatter, fused on the CPU
        return jnp.sort(y * 2)[i] + 1      # a sort, and a fused gather
    return jax.jit(f).lower(jnp.zeros(64), jnp.arange(8),
                            jnp.ones(8)).compile().as_text()


def test_hlo_table_sees_into_fusions():
    table = trace.hlo_table(_compiled_text())
    ran = [ops for ops, _ in table.values()]
    for op in ("scatter", "gather", "sort"):
        assert any(op in ops for ops in ran), op
    # a fusion's entry holds what its computation runs
    assert any("fusion" in ops and ({"scatter", "gather"} & ops)
               for ops in ran)
    assert not any(pallas for _, pallas in table.values())


def test_classify_names_a_fusion_by_what_it_fuses():
    table = {"fusion.9": (frozenset({"fusion", "gather", "add"}), False),
             "custom-call.3": (frozenset({"custom-call"}), True)}
    key, op, ran, pallas = trace.classify(
        "fusion.9", {"long_name": "%fusion.9 = f32[8]{0} fusion(f32[8]{0} "
                     "%a), kind=kLoop, calls=%fc"}, table)
    assert (key, op, pallas) == ("fusion[gather]", "fusion", False)
    assert "gather" in ran
    assert trace.classify("custom-call.3", {}, table)[3] is True
    # not in the table: the event's own HLO text decides
    assert trace.classify("sort.5", {}, table)[:3] == (
        "sort", "sort", frozenset({"sort"}))


@pytest.fixture(scope="module")
def v5e():
    """An excerpt of a traced run of sc2-gspar-ef on a TPU v5e chip, with
    the compiled step's HLO rows of its events and the reduction of the
    whole trace and of the excerpt as recorded."""
    import json

    from jax.profiler import ProfileData
    recorded = json.loads((DATA / "v5e-sc2-gspar-ef.json").read_text())
    table = {n: (frozenset(ops), pallas)
             for n, (ops, pallas) in recorded["hlo_rows"].items()}
    red = trace.reduce(ProfileData.from_text_proto(
        (DATA / "v5e-sc2-gspar-ef.pbtxt").read_text()), hlo=table)
    return red, recorded


def test_v5e_events_named_by_hlo_text():
    name = ("%sort.1 = (s32[8]{0:T(1024)}, f32[8]{0:T(1024)}) sort(s32[8]{0:"
            "T(1024)} %b.4, f32[8]{0:T(1024)} %b.5), dimensions={0}")
    assert trace.instruction(name) == "sort.1"
    assert trace.opcode(name, {}) == "sort"
    tup = ("%fusion.19 = (f32[]{:T(128)}, /*index=5*/bf16[8]{0:T(1024)}) "
           "fusion(bf16[8]{0:T(1024)} %c.3), kind=kLoop, calls=%fc")
    assert (trace.instruction(tup), trace.opcode(tup, {})) == (
        "fusion.19", "fusion")
    assert trace.base_name("broadcast.505.clone") == "broadcast"


def test_v5e_reduction_as_recorded(v5e):
    red, recorded = v5e
    want = recorded["excerpt"]
    for k in ("window_s", "busy_s", "pallas_s", "devices"):
        assert red[k] == pytest.approx(want[k]), k
    assert set(red["ops"]) == set(want["ops"])
    for k, op in want["ops"].items():
        assert red["ops"][k]["seconds"] == pytest.approx(op["seconds"]), k


def test_v5e_ops_attributed(v5e):
    red, _ = v5e
    ops = red["ops"]
    # the Pallas kernels are custom calls named after the kernel
    assert red["pallas_s"] > 0
    assert ops["compact_emit_lam"]["opcode"] == "custom-call"
    # the compaction's sorts stand alone; its scatters and the codec's
    # gathers run inside fusions, found through the compiled HLO
    assert ops["sort"]["opcode"] == "sort"
    assert "scatter" in ops["fusion[scatter]"]["runs"]
    assert "gather" in ops["fusion[custom-call,gather]"]["runs"]
    sgs = sum(op["seconds"] for op in ops.values()
              if {"scatter", "gather", "sort"} & set(op["runs"]))
    assert sgs > 0.9 * red["busy_s"]
