"""DeepSeek-V2-Lite's options in the program against the benchmark's plain
float32 reference (``chipbench/families/deepseek2.py``), on the CPU with
seeded random weights: the MoE layer holding a share of the routed experts
(routed over all of them, no dropping, top-k weights not renormalised but
scaled, the balance term over the batch or per row), the guide's share
test, MLA without q-LoRA with latent norms and YaRN, a tiny whole model's
loss and every leaf's gradient, and the ``expert_slot_fill`` counter.

Tolerances: both sides compute in float32 (matmuls at ``highest``), so
they differ by summation order alone: 2e-5 relative on activations, 1e-5
on losses, 1e-4 of a leaf's norm on its gradient."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import job, reference, weights  # noqa: E402
from chipbench.families import deepseek2 as ds  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.common import split_params  # noqa: E402
from repro.train.loss import lm_loss, shift_targets  # noqa: E402

MM = reference._op(False)
TINY = ROOT / "chipbench" / "tests" / "data" / "tiny-dsv2lite.json"
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def small(**over):
    """One Lite-option layer at a width small enough for a CPU test."""
    conf = {
        "family": "deepseek2", "hidden_size": 32, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": None, "kv_lora_rank": 8,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "rope_theta": 10000.0, "rope_scaling": YARN, "latent_norm": True,
        "rms_norm_eps": 1e-6, "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "num_hidden_layers": 3,
        "intermediate_size": 48, "hidden_act": "silu",
        "moe_intermediate_size": 16, "n_routed_experts": 16,
        "experts_held": 16, "expert_offset": 0, "num_experts_per_tok": 3,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "routed_scaling_factor": 2.5, "scoring_func": "softmax",
        "topk_method": "greedy", "capacity_factor": None, "seq_aux": True,
        "aux_loss_alpha": 0.001, "router_z_loss_coef": 0.0,
        "vocab_size": 64}
    conf.update(over)
    return conf


def program_moe(conf) -> moe_lib.MoEConfig:
    return moe_lib.MoEConfig(
        d_model=conf["hidden_size"], d_expert=conf["moe_intermediate_size"],
        num_experts=conf["n_routed_experts"],
        top_k=conf["num_experts_per_tok"],
        num_shared=conf["n_shared_experts"],
        capacity_factor=conf["capacity_factor"], act="silu",
        normalize_weights=conf["norm_topk_prob"],
        routed_scaling_factor=conf["routed_scaling_factor"],
        seq_aux=conf["seq_aux"], aux_loss_coef=conf["aux_loss_alpha"],
        z_loss_coef=conf["router_z_loss_coef"],
        experts_held=conf["experts_held"],
        expert_offset=conf["expert_offset"])


def block_params(conf, kind="mla", seed=0):
    init = {"default": ["normal", 0.2],
            "rules": [["router$", "normal", 0.5], ["scale$", "normal", 0.1]]}
    return weights.make(init, jax.random.key(seed), ds.block(conf, kind),
                        jnp.float32)


def as_program(p: dict, prefix: str) -> dict:
    """Family leaves under ``prefix`` as the program's nested tree."""
    out: dict = {}
    for path, v in p.items():
        if not path.startswith(prefix):
            continue
        *heads, leaf = path[len(prefix):].split("/")
        node = out
        for h in heads:
            node = node.setdefault(h, {})
        node[leaf] = v
    return out


def held_share(p: dict, off: int, held: int) -> dict:
    return {k: (v[off:off + held] if k in ds.ROUTED else v)
            for k, v in p.items()}


def rows(conf, s, b, seed=1):
    return jax.random.normal(jax.random.key(seed),
                             (b, s, conf["hidden_size"]), jnp.float32)


# ---------------------------------------------------------------------------
# the MoE layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_aux", [True, False], ids=["seq_aux", "batch"])
@pytest.mark.parametrize("held,off", [(8, 0), (8, 8), (16, 0)],
                         ids=["held0-8", "held8-16", "all16"])
def test_moe_share_matches_family(held, off, seq_aux):
    """8 of 16 experts at offsets 0 and 8, or all 16, no capacity, weights
    not renormalised and scaled by 2.5: output and router loss."""
    conf = small(experts_held=held, expert_offset=off, seq_aux=seq_aux)
    full = block_params(small())
    p = held_share(full, off, held)
    x = rows(conf, 24, b=2)
    y_ref, st = jax.vmap(lambda r: ds.moe(conf, MM, p, r))(x)
    with jax.default_matmul_precision("highest"):
        y, aux = moe_lib.moe_ffn(as_program(p, "ffn/"), program_moe(conf), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    assert float(aux) == pytest.approx(float(ds.router_loss(conf, [st])),
                                       rel=1e-5)


def test_program_shares_sum_to_the_layer():
    """Guide section 4 on the program: 8 chips each hold 2 of 16 experts;
    their outputs, with the shared experts counted once, add up to the
    uncut layer's."""
    conf = small()
    p = block_params(conf)
    x = rows(conf, 32, b=2)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_lib.moe_ffn(as_program(p, "ffn/"), program_moe(conf),
                                   x)
        shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(
            jnp.einsum("bsd,df->bsf", x, p["ffn/shared/gate"]))
            * jnp.einsum("bsd,df->bsf", x, p["ffn/shared/up"]),
            p["ffn/shared/down"])
        total = shared
        for off in range(0, 16, 2):
            part = dict(conf, experts_held=2, expert_offset=off)
            y, _ = moe_lib.moe_ffn(as_program(held_share(p, off, 2), "ffn/"),
                                   program_moe(part), x)
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bad", [{"experts_held": 8, "expert_offset": 12}])
def test_moe_refuses_what_it_does_not_model(bad):
    with pytest.raises(ValueError):
        moe_lib.MoEConfig(d_model=8, d_expert=4, num_experts=16, top_k=2,
                          **bad)


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_lora,latent_norm,scaling", [
    (None, True, YARN), (12, True, YARN), (None, False, None)],
    ids=["lite", "q_lora-norms-yarn", "plain"])
def test_mla_matches_family(q_lora, latent_norm, scaling):
    conf = small(q_lora_rank=q_lora, latent_norm=latent_norm,
                 rope_scaling=scaling)
    p = block_params(conf, seed=4)
    x = rows(conf, 16, b=1, seed=5)[0]
    want = ds.mla(conf, MM, p, x)
    cfg = attn.MLAConfig(
        d_model=32, num_heads=2, kv_lora=8, q_lora=q_lora or 0, qk_nope=8,
        qk_rope=4, v_dim=8, rope_theta=10000.0, latent_norm=latent_norm,
        rope_scaling=None if scaling is None else tuple(scaling.items()))
    with jax.default_matmul_precision("highest"):
        got = attn.mla_train(as_program(p, "attn/"), cfg, x[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# a tiny whole model
# ---------------------------------------------------------------------------

def _tiny():
    conf = json.loads(TINY.read_text())
    conf["dtype"] = "float32"
    _, cfg = job.model_config(conf)
    return conf, cfg


def test_tiny_model_loss_and_gradients_match_reference():
    conf, cfg = _tiny()
    shapes = split_params(jax.eval_shape(lambda k: tf.init_model(k, cfg),
                                         jax.random.key(0)))[0]
    paths = weights.paths_of(shapes)
    treedef = jax.tree_util.tree_structure(shapes)
    made = weights.make(conf["init"], jax.random.key(7),
                        reference.layout(conf), jnp.float32)
    params = jax.tree_util.tree_unflatten(treedef, [made[p] for p in paths])
    tokens = jax.random.randint(jax.random.key(8), (2, 32), 0,
                                conf["vocab_size"])

    def program_loss(params):
        logits, aux = tf.forward_train(params, cfg, {"tokens": tokens})
        t, m = shift_targets(tokens)
        return lm_loss(logits, t, m) + aux

    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(program_loss)(params)
    ref_loss, ref_g = jax.value_and_grad(
        lambda p: reference.loss_fn(conf, p, tokens))(made)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for path, leaf in zip(paths, jax.tree.leaves(g)):
        want = np.asarray(ref_g[path])
        gap = np.linalg.norm(np.asarray(leaf) - want)
        assert gap <= 1e-4 * np.linalg.norm(want), (path, gap)


# ---------------------------------------------------------------------------
# expert_slot_fill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity_factor", [None, 1.0])
def test_slot_fill_is_a_hand_count(capacity_factor):
    """A fixed routing: token t scores 5 on expert a_t and 3 on b_t, so its
    top-2 is {a_t, b_t}. This layer holds experts 2-5. The fill is the
    choices the held experts compute over their slot rows: without a
    capacity each holds a row's length of slots; with one, an expert
    computes at most ``capacity`` choices."""
    e, s, b = 8, 12, 2
    a = np.array([[0, 2, 3, 3, 5, 7, 1, 2, 2, 4, 6, 3],
                  [5, 5, 5, 5, 5, 1, 0, 7, 6, 2, 3, 4]])
    bb = (a + 3) % e
    x = 5.0 * jax.nn.one_hot(a, e) + 3.0 * jax.nn.one_hot(bb, e)
    cfg = moe_lib.MoEConfig(d_model=e, d_expert=4, num_experts=e, top_k=2,
                            capacity_factor=capacity_factor,
                            experts_held=4, expert_offset=2)
    p = {"router": jnp.eye(e), "w_gate": jnp.ones((4, e, 4)),
         "w_up": jnp.ones((4, e, 4)), "w_down": jnp.ones((4, 4, e))}
    _, _, fill = moe_lib.moe_layer(p, cfg, x)
    cap = cfg.capacity(s)
    computed = sum(min(int(np.sum((a[r] == j) | (bb[r] == j))), cap)
                   for r in range(b) for j in range(2, 6))
    assert float(fill) == pytest.approx(computed / (b * 4 * cap))
    if capacity_factor is None:
        assert cap == s


def test_slot_fill_counter_is_the_mean_over_moe_layers():
    conf, cfg = _tiny()
    params = split_params(tf.init_model(jax.random.key(3), cfg))[0]
    tokens = jax.random.randint(jax.random.key(4), (2, 32), 0,
                                conf["vocab_size"])
    _, _, counters = tf.forward_train_counted(params, cfg,
                                              {"tokens": tokens})
    fills = []
    real = moe_lib.moe_layer

    def spy(p, c, x):
        out = real(p, c, x)
        jax.debug.callback(lambda f: fills.append(float(f)), out[2])
        return out
    try:
        moe_lib.moe_layer = spy
        tf.forward_train_counted(params, cfg, {"tokens": tokens})
    finally:
        moe_lib.moe_layer = real
    assert len(fills) == cfg.num_periods == 2
    assert float(counters["expert_slot_fill"]) == pytest.approx(
        100.0 * float(np.mean(fills)), rel=1e-6)
    assert 0 < float(counters["expert_slot_fill"]) < 100
