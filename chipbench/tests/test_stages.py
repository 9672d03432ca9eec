"""Device time per stage of the program (``chipbench/stages.py``): the
stage table read from a compiled module's HLO metadata, the split of a
hand-made trace with known answers, and the stage of every scatter,
gather, sort and custom call in the tiny cell's compiled step."""
import json
import pathlib

import pytest

from chipbench import stages

import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
STEP_STAGES = ("model", "compress", "compact", "pack", "decode", "apply",
               "optimizer")


def test_innermost_stage_names_the_instruction():
    assert stages.stage_of(
        "jit(step)/transpose(jvp(stage.model))/stage.compact/scatter") \
        == "compact"
    assert stages.stage_of("jit(step)/transpose(jvp(stage.model))/dot") \
        == "model"
    assert stages.stage_of("jit(step)/stage.compress/vmap(jit(gspar_emit))"
                           "/stage.compact/jit(cumsum)") == "compact"
    assert stages.stage_of("jit(step)/psum") is None


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    table = stages.hlo_stages((DATA / "synthetic_stages.hlo.txt").read_text())
    pd = ProfileData.from_text_proto(
        (DATA / "synthetic_stages.pbtxt").read_text())
    return table, stages.split(pd, table)


def test_hlo_stages(synthetic):
    table, _ = synthetic
    assert table["fusion.1"] == "apply"
    assert table["scatter.0"] == "apply"        # inside the fusion
    assert table["convolution.2"] == "model"    # the backward pass
    assert table["sort.3"] == "compact"         # innermost wins
    assert table["copy.4"] is None              # no metadata
    assert table["custom-call.5"] == "compress"


def test_stages_the_compiler_dropped():
    """Instructions the compiler makes without metadata take the stage of
    what makes their operands, else of what reads them."""
    table = stages.hlo_stages(
        (DATA / "synthetic_stages_inferred.hlo.txt").read_text())
    # a sort put in to lower a scatter: made from the compact kernel's
    # output; the constant's model metadata casts no vote
    assert table["sort.1"] == "compact"
    # a fusion wrapping a fusion whose root scatter lost its metadata
    assert table["fusion.2"] == "compact"
    assert table["scatter.9"] == "compact"
    assert table["fusion.3"] == "decode"        # its fused root's stage
    assert table["copy.4"] == "decode"          # made by a decode fusion
    # made by decode and model alike: a tie, so its reader decides
    assert table["tie"] == "optimizer"
    # no maker, and read by optimizer and apply alike: unscoped
    assert table["a"] is None
    assert table["m"] == "model"                # its own metadata


def test_split_sums_per_stage(synthetic):
    _, red = synthetic
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(10e-6)
    secs = red["stages"]
    # 2 us and the 0.5 us inside the window of a run cut by its end; the
    # run at 20 us lies outside
    assert secs["apply"] == pytest.approx(2.5e-6)
    assert secs["model"] == pytest.approx(1e-6)
    assert secs["compact"] == pytest.approx(0.5e-6)
    assert secs["compress"] == pytest.approx(1e-6)
    # the unscoped copy and the instruction the module does not hold
    assert secs[None] == pytest.approx(0.75e-6)
    assert set(secs) == {"apply", "model", "compact", "compress", None}
    assert sum(secs.values()) == pytest.approx(red["busy_s"])


def test_per_step(synthetic):
    _, red = synthetic
    out = stages.per_step(red, steps=2)
    assert out["stage_ms"]["apply"] == pytest.approx(2.5e-6 * 1e3 / 2)
    assert out["stage_ms"]["unscoped"] == pytest.approx(0.75e-6 * 1e3 / 2)
    assert "pack" not in out["stage_ms"]        # no time: left out
    assert out["sum_ms"] == pytest.approx(out["busy_ms"])
    assert out["unscoped_share"] == pytest.approx(100 * 0.75 / 5.75)


@pytest.fixture(scope="module", params=["sync", "overlap"])
def tiny_step(request):
    """The tiny cell's compiled step on the CPU, with the given exchange:
    its HLO text's stage table and opcode table."""
    import jax

    from chipbench import data, job as job_lib, trace
    cell = tiny.cell("tiny-sc2")
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["compression"]["exchange"] = request.param
    job = job_lib.build(cell.config, traffic, (1, 1), jax.devices()[:1])
    feed = data.step_feed(cell.config["vocab_size"], job.global_batch,
                          job.seq)
    keys = data.streams(1)
    with jax.set_mesh(job.mesh):
        state = jax.eval_shape(job.init, keys["weights"])
        batch, key = feed(keys, 0)
        text = job.step.lower(*state, batch, key).compile().as_text()
    return stages.hlo_stages(text), trace.hlo_table(text)


def test_tiny_step_stages(tiny_step):
    table, ops = tiny_step
    notable = {"scatter", "gather", "sort", "custom-call"}
    # each such instruction, and each fusion that runs one (a fusion's
    # device event is what a trace times)
    runs = [n for n, (ran, _) in ops.items() if ran & notable]
    assert any(ops[n][0] & {"scatter"} for n in runs)
    unscoped = [n for n in runs if table[n] is None]
    assert not unscoped, unscoped
    found = set(table.values())
    assert set(STEP_STAGES) <= found, set(STEP_STAGES) - found


@pytest.fixture(scope="module")
def v5e():
    """An excerpt of a traced run of sc2-gspar-ef on a TPU v5e chip, with
    the stage of each kept event's instruction (read from the compiled
    step's whole HLO) and the split of the whole trace as recorded."""
    from jax.profiler import ProfileData
    recorded = json.loads((DATA / "v5e-sc2-stages.json").read_text())
    pd = ProfileData.from_text_proto(
        (DATA / "v5e-sc2-stages.pbtxt").read_text())
    return stages.split(pd, recorded["stage_rows"]), recorded


def test_v5e_excerpt_as_recorded(v5e):
    red, recorded = v5e
    want = {(None if k == "None" else k): v
            for k, v in recorded["excerpt"]["stages"].items()}
    assert set(red["stages"]) == set(want)
    for k, v in want.items():
        assert red["stages"][k] == pytest.approx(v), k
    out = stages.per_step(red, recorded["steps"])
    assert out["unscoped_share"] < 5


def test_v5e_whole_trace_covered(v5e):
    _, recorded = v5e
    whole = recorded["whole"]
    # every stage of the step spent device time; the stages and the
    # unscoped rest add up to the busy time per step within 1%
    assert set(STEP_STAGES) <= set(whole["stage_ms"])
    assert whole["sum_ms"] == pytest.approx(whole["busy_ms"], rel=0.01)
    assert whole["unscoped_share"] < 5
