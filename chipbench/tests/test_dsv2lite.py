"""The DeepSeek-V2-Lite options through the harness at a size the CPU
holds (``tiny-dsv2lite``: no q-LoRA, KV latent norm, YaRN, 4 of 8 routed
experts held from expert 2, top-3 without renormalisation, per-row
balance term, no dropping): the program's compressed step against the
family file's float32 reference. Sound, the run comes out correct; with
half of each row left out of the loss, or the largest leaf's synced
gradient doubled, it comes out not correct."""
import jax
import pytest

from chipbench import job, reference, run as run_lib

import tiny

SEED = 2**33 + 29


def _run():
    result, lines = run_lib.run(tiny.cell("tiny-dsv2lite"), SEED, 0.5, 0,
                                require_chip=False)
    assert [line.split()[1] for line in lines[-4:]] == [
        "loss_gap", "later_loss_gap", "grad_gap", "change_gap"]
    return result


def test_layout_is_the_programs():
    """The file's sizes are the program's (``check_sizes``) and the
    program's tree is the family's layout: the router over all 8 experts,
    4 held."""
    conf = tiny.cell("tiny-dsv2lite").config
    _, cfg = job.model_config(conf)
    assert cfg.moe.held == 4 and cfg.moe.expert_offset == 2
    lay = reference.layout(conf)
    assert lay["blocks/b0_mla/ffn/router"] == (2, 128, 8)
    assert lay["blocks/b0_mla/ffn/w_down"] == (2, 4, 64, 128)
    assert lay["prelude/p0_mla_dense/attn/kv_norm/scale"] == (32,)
    assert "prelude/p0_mla_dense/attn/q_down" not in lay


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0


def test_half_batch(monkeypatch):
    from repro.train import step as step_lib
    loss = step_lib.lm_loss

    def half(logits, targets, mask):
        return loss(logits, targets,
                    mask.at[..., mask.shape[-1] // 2:].set(0.0))
    monkeypatch.setattr(step_lib, "lm_loss", half)
    assert _run()["correct"] is False


def test_answer_altered(monkeypatch):
    from repro.train import step as step_lib
    sync = step_lib.sync_tree

    def altered(*a, **kw):
        synced, *rest = sync(*a, **kw)
        leaves, tdef = jax.tree_util.tree_flatten(synced)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves[big] = leaves[big] * 2
        return (jax.tree_util.tree_unflatten(tdef, leaves), *rest)
    monkeypatch.setattr(step_lib, "sync_tree", altered)
    assert _run()["correct"] is False


@pytest.mark.parametrize("key", ["moe", "rope_scaling"])
def test_program_that_departs_is_refused(key):
    """A program config that departs from the file stops the run before
    anything is compiled."""
    conf = dict(tiny.cell("tiny-dsv2lite").config)
    conf["program"] = {"arch": "deepseek-v2-lite", "replace": dict(
        conf["program"]["replace"],
        **({"moe": dict(conf["program"]["replace"]["moe"], expert_offset=0)}
           if key == "moe" else {}))}
    if key == "rope_scaling":
        conf["rope_scaling"] = None
    with pytest.raises(SystemExit, match="differs from the file"):
        job.model_config(conf)


def test_limits_between_readings():
    """As ``test_limits.py`` holds the other cells' limits: above every
    sound reading, and the control and each fault above some limit."""
    import json

    from chipbench import compare
    entry = json.loads((tiny.DATA / "tiny-dsv2lite.limits.json").read_text())
    limits, readings = entry["limits"], entry["readings"]
    assert set(limits) == set(compare.NAMES)
    for k in compare.NAMES:
        assert readings["program_max"][k] < limits[k], k
    uppers = {"control": readings["control_min"], **readings["faults_min"]}
    for what, upper in uppers.items():
        assert any(upper[k] > limits[k] for k in upper), what


def test_slot_fill_reader():
    """The mean of the steps' counter; nothing where a program or a model
    has no such counter (the parent's program, sc2)."""
    read = run_lib._load_reader("expert_slot_fill")
    steps = [{"loss": 1.0, "expert_slot_fill": 9.0},
             {"loss": 1.0, "expert_slot_fill": 10.0}]
    assert read({"per_step": steps}) == pytest.approx(9.5)
    assert read({"per_step": [{"loss": 1.0}]}) is None
    assert read({"per_step": []}) is None
