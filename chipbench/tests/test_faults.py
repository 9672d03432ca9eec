"""A whole run at a size the CPU holds, with the chip check skipped, of
each test cell (StarCoder2, RWKV-6, DeepSeek-V2 with its router loss and
dropping at capacity): sound,
it comes out correct; with the timed path broken underneath it comes out
not correct, once for each fault a one-chip training cell can have.

- a step that returns its state unchanged;
- half of the batch left out, the mean taken over the rest;
- an answer altered where it is produced (the synced gradient of the
  largest leaf doubled as sync_tree hands it to the optimizer).

The exchange between chips cannot be left out of a one-chip cell: with
one worker the all-gather returns the worker's own buffers."""
import jax
import pytest

from chipbench import run as run_lib

import tiny

SEED = 2**33 + 17
CONFIGS = pytest.mark.parametrize("config", ["tiny-sc2", "tiny-rwkv6",
                                             "tiny-dsv2"])


def _run(config):
    result, lines = run_lib.run(tiny.cell(config), SEED, 0.5, 0,
                                require_chip=False)
    assert [line.split()[1] for line in lines[-4:]] == [
        "loss_gap", "later_loss_gap", "grad_gap", "change_gap"]
    return result


@CONFIGS
def test_sound_run_is_correct(config):
    result = _run(config)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


@CONFIGS
def test_unchanged_state(monkeypatch, config):
    from repro.train import step as step_lib
    make = step_lib.make_compressed_train_step

    def broken(*a, **kw):
        inner = make(*a, **kw)

        def step(params, opt_state, ef_state, batch, key):
            *_, metrics = inner(params, opt_state, ef_state, batch, key)
            return params, opt_state, ef_state, metrics
        return step
    monkeypatch.setattr(step_lib, "make_compressed_train_step", broken)
    result = _run(config)
    assert result["correct"] is False
    assert result["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@CONFIGS
def test_half_batch(monkeypatch, config):
    from repro.train import step as step_lib
    loss = step_lib.lm_loss

    def half(logits, targets, mask):
        return loss(logits, targets,
                    mask.at[..., mask.shape[-1] // 2:].set(0.0))
    monkeypatch.setattr(step_lib, "lm_loss", half)
    assert _run(config)["correct"] is False


@CONFIGS
def test_answer_altered(monkeypatch, config):
    from repro.train import step as step_lib
    sync = step_lib.sync_tree

    def altered(*a, **kw):
        synced, *rest = sync(*a, **kw)
        leaves, tdef = jax.tree_util.tree_flatten(synced)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves[big] = leaves[big] * 2
        return (jax.tree_util.tree_unflatten(tdef, leaves), *rest)
    monkeypatch.setattr(step_lib, "sync_tree", altered)
    assert _run(config)["correct"] is False
