"""BENCHMARK.json names only files that exist, and each configuration
file's sizes are the program's."""
import json

import pytest

from chipbench import job, reference, spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    cell = spec.load(w["name"], BENCH)
    assert cell.cell["mesh"][0] * cell.cell["mesh"][1] == cell.chips
    assert set(cell.cell["limits"]) == {"loss_gap", "later_loss_gap", "grad_gap",
                                      "change_gap"}
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert cell.per_layer


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_matches_program(c):
    conf = json.loads((spec.ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    _, cfg = job.model_config(conf)          # raises where sizes differ
    import jax
    from repro.models import transformer as tf
    from repro.models.common import split_params
    from chipbench import weights
    shapes = split_params(jax.eval_shape(lambda k: tf.init_model(k, cfg),
                                         jax.random.key(0)))[0]
    got = dict(zip(weights.paths_of(shapes),
                   (tuple(s.shape) for s in jax.tree.leaves(shapes))))
    assert got == reference.layout(conf)


def test_seed_keeps_high_bits():
    import jax
    from chipbench import data
    a = jax.random.key_data(data.seed_key(5))
    b = jax.random.key_data(data.seed_key(2**32 + 5))
    assert (a != b).any()
