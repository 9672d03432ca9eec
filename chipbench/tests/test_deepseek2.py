"""The DeepSeek-V2 family file against closed forms and the program, at
CPU sizes: YaRN, the chip's share of the experts, dropping at capacity,
the router loss, and the layout of the published options that the
program cannot run yet."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, flops, reference, weights
from chipbench.families import deepseek2 as ds

import tiny

MM = reference._op(False)

# the published DeepSeek-V2-Lite config (huggingface.co/deepseek-ai/
# DeepSeek-V2-Lite), as the chip's share would run it: 1 dense + 4 MoE
# layers, 8 of the 64 routed experts held, an eighth of the vocabulary
LITE_SHARE = {
    "family": "deepseek2", "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 16, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "latent_norm": True, "rms_norm_eps": 1e-06,
    "attention_bias": False, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_hidden_layers": 5, "intermediate_size": 10944, "hidden_act": "silu",
    "moe_intermediate_size": 1408, "n_routed_experts": 64, "experts_held": 8,
    "expert_offset": 0, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "topk_method": "greedy",
    "capacity_factor": None, "seq_aux": True, "aux_loss_alpha": 0.001,
    "router_z_loss_coef": 0.0, "vocab_size": 12800,
    "tie_word_embeddings": True}


def small(**over):
    """A MoE layer small enough to loop over by hand."""
    conf = dict(LITE_SHARE, hidden_size=32, num_attention_heads=2,
                kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=8, moe_intermediate_size=16, n_routed_experts=16,
                experts_held=16, num_experts_per_tok=3, n_shared_experts=2,
                intermediate_size=48, num_hidden_layers=3, vocab_size=64,
                rope_scaling=None)
    conf.update(over)
    return conf


def moe_params(conf, seed=0):
    shapes = {k: s for k, s in ds.block(conf, "mla").items()
              if k.startswith("ffn/")}
    init = {"default": ["normal", 0.2], "rules": [["router$", "normal", 0.5]]}
    return weights.make(init, jax.random.key(seed), shapes, jnp.float32)


def rows(conf, s, b=None, seed=1):
    shape = (s, conf["hidden_size"]) if b is None else (
        b, s, conf["hidden_size"])
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scaling", [
    None,
    LITE_SHARE["rope_scaling"],
    dict(LITE_SHARE["rope_scaling"], mscale=1.0, mscale_all_dim=0.0,
         factor=16, original_max_position_embeddings=2048),
], ids=["plain", "lite", "mscale-only"])
def test_yarn_against_formula(scaling):
    conf = dict(LITE_SHARE, rope_scaling=scaling)
    inv, cos_sin, softmax = ds.rope_frequencies(conf)
    dim, base = 64, 10000.0
    plain = base ** (-np.arange(0, dim, 2) / dim)
    if scaling is None:
        want, want_cs, want_sm = plain, 1.0, 1.0
    else:
        s, orig = scaling["factor"], scaling["original_max_position_embeddings"]

        # the dimension whose wavelength turns r times in orig positions
        def dim_of(r):
            return dim * math.log(orig / (r * 2 * math.pi)) / (
                2 * math.log(base))
        lo = max(math.floor(dim_of(scaling["beta_fast"])), 0)
        hi = min(math.ceil(dim_of(scaling["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
        # fast dimensions keep their frequency, slow ones are divided by s
        want = plain * (1 - ramp) + plain / s * ramp

        def msc(m):
            return 0.1 * m * math.log(s) + 1.0
        want_cs = msc(scaling["mscale"]) / (
            msc(scaling["mscale_all_dim"]) if scaling["mscale_all_dim"]
            else 1.0)
        want_sm = (msc(scaling["mscale_all_dim"]) ** 2
                   if scaling["mscale_all_dim"] else 1.0)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert cos_sin == pytest.approx(want_cs, rel=1e-12)
    assert softmax == pytest.approx(want_sm, rel=1e-12)
    if scaling is LITE_SHARE["rope_scaling"]:
        # DeepSeek-V2's mscale = mscale_all_dim: cos and sin unscaled, the
        # softmax scale times (0.1 * 0.707 * ln 40 + 1)^2
        assert cos_sin == 1.0
        assert softmax == pytest.approx(1.2608037 ** 2, rel=1e-6)


def test_rope_rotates_by_position():
    conf = small(rope_scaling=LITE_SHARE["rope_scaling"])
    inv, cs, _ = ds.rope_frequencies(conf)
    x = rows(conf, 5)[:, None, :4]                      # [S, 1, rope]
    out = ds._rope(x, inv, cs)
    for t in range(5):
        for i in range(2):
            a = t * float(inv[i])
            x1, x2 = float(x[t, 0, i]), float(x[t, 0, i + 2])
            assert float(out[t, 0, i]) == pytest.approx(
                x1 * math.cos(a) - x2 * math.sin(a), abs=1e-5)
            assert float(out[t, 0, i + 2]) == pytest.approx(
                x2 * math.cos(a) + x1 * math.sin(a), abs=1e-5)


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity_factor", [None, 1.0])
def test_expert_shares_sum_to_the_layer(capacity_factor):
    """Guide section 4's share test: 8 chips each hold 2 of 16 experts;
    their parts, with the shared experts counted once, add up to the
    whole layer."""
    conf = small(capacity_factor=capacity_factor)
    p, x = moe_params(conf), rows(conf, 32)
    whole, st = ds.moe(conf, MM, p, x)
    if capacity_factor:
        assert float(st["dropped"]) > 0
    shared = ds.swiglu(MM, x, p["ffn/shared/gate"], p["ffn/shared/up"],
                       p["ffn/shared/down"])
    total = shared
    for off in range(0, 16, 2):
        part = dict(conf, experts_held=2, expert_offset=off)
        q = {k: (v[off:off + 2] if k in ("ffn/w_gate", "ffn/w_up",
                                         "ffn/w_down") else v)
             for k, v in p.items()}
        y, _ = ds.moe(part, MM, q, x)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm,scale", [(False, 2.5), (True, 2.5)])
def test_no_capacity_is_a_loop_over_chosen_experts(norm, scale):
    """With ``capacity_factor`` null, each token's routed output is the sum
    over its top-k experts that this chip holds (here 6 from offset 4) of
    the router's weight times the expert's SwiGLU: renormalised weights,
    or else weights times ``routed_scaling_factor``, as DeepSeek-V2's
    ``MoEGate`` has it."""
    conf = small(norm_topk_prob=norm, routed_scaling_factor=scale,
                 experts_held=6, expert_offset=4)
    full = moe_params(dict(conf, experts_held=16, expert_offset=0))
    p = {k: (v[4:10] if k in ds.ROUTED else v) for k, v in full.items()}
    x = rows(conf, 24)
    y, _ = ds.moe(conf, MM, p, x)
    f64 = {k: np.asarray(v, np.float64) for k, v in p.items()}
    xs = np.asarray(x, np.float64)

    def silu(a):
        return a / (1 + np.exp(-a))
    for t in range(24):
        logits = xs[t] @ f64["ffn/router"]
        pr = np.exp(logits - logits.max())
        pr /= pr.sum()
        top = np.argsort(-pr)[:3]
        w = pr[top] / pr[top].sum() if norm else pr[top] * scale
        want = silu(xs[t] @ f64["ffn/shared/gate"]) * (
            xs[t] @ f64["ffn/shared/up"]) @ f64["ffn/shared/down"]
        for e, we in zip(top, w):
            if 4 <= e < 10:
                j = e - 4
                h = silu(xs[t] @ f64["ffn/w_gate"][j]) * (
                    xs[t] @ f64["ffn/w_up"][j])
                want = want + we * (h @ f64["ffn/w_down"][j])
        np.testing.assert_allclose(np.asarray(y[t]), want, rtol=2e-5,
                                   atol=2e-5)


def _program_moe(conf):
    from repro.models import moe as moe_lib
    return moe_lib.MoEConfig(
        d_model=conf["hidden_size"], d_expert=conf["moe_intermediate_size"],
        num_experts=conf["n_routed_experts"],
        top_k=conf["num_experts_per_tok"],
        num_shared=conf["n_shared_experts"],
        capacity_factor=conf["capacity_factor"], act="silu",
        normalize_weights=conf["norm_topk_prob"],
        aux_loss_coef=conf["aux_loss_alpha"],
        z_loss_coef=conf["router_z_loss_coef"])


@pytest.mark.parametrize("batch", [1, 2])
def test_router_loss_and_drops_are_the_programs(batch):
    """At float32 the program's MoE FFN gives the family's output, drops
    the same choices at capacity, and adds the same router loss over a
    batch of rows."""
    from repro.models import moe as moe_lib
    conf = small(n_routed_experts=4, experts_held=4, num_experts_per_tok=2,
                 n_shared_experts=1, norm_topk_prob=True, capacity_factor=1.0,
                 seq_aux=False, aux_loss_alpha=0.01, router_z_loss_coef=1e-3)
    p, x = moe_params(conf), rows(conf, 64, b=batch)
    y, st = jax.vmap(lambda r: ds.moe(conf, MM, p, r))(x)
    assert float(jnp.sum(st["dropped"])) > 0
    prog = {"router": p["ffn/router"], "w_gate": p["ffn/w_gate"],
            "w_up": p["ffn/w_up"], "w_down": p["ffn/w_down"],
            "shared": {k: p["ffn/shared/" + k] for k in ("gate", "up",
                                                          "down")}}
    with jax.default_matmul_precision("highest"):
        y_prog, aux = moe_lib.moe_ffn(prog, _program_moe(conf), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_prog),
                               rtol=1e-5, atol=1e-5)
    assert float(ds.router_loss(conf, [st])) == pytest.approx(
        float(aux), rel=1e-6)


def test_seq_aux_averages_rows():
    conf = small(seq_aux=True, aux_loss_alpha=0.001, router_z_loss_coef=0.0)
    p, x = moe_params(conf), rows(conf, 16, b=3)
    _, st = jax.vmap(lambda r: ds.route(conf, MM, p, r))(x)
    per_row = [float(ds.router_loss(conf, [jax.tree.map(
        lambda a: a[i:i + 1], st)])) for i in range(3)]
    assert float(ds.router_loss(conf, [st])) == pytest.approx(
        sum(per_row) / 3, rel=1e-6)
    # one row: the same as the batch-combined term
    one = jax.tree.map(lambda a: a[:1], st)
    assert float(ds.router_loss(conf, [one])) == pytest.approx(
        float(ds.router_loss(dict(conf, seq_aux=False), [one])), rel=1e-6)


def test_reference_drops_at_capacity_in_the_test_cell():
    """tiny-dsv2's capacity factor drops choices at seq 64 in the rows and
    weights of the run that ``test_faults.py`` makes."""
    cell = tiny.cell("tiny-dsv2")
    conf, traffic = cell.config, cell.traffic
    keys = data.streams(2**33 + 17)
    tokens = data.step_feed(conf["vocab_size"], traffic["batch_per_chip"],
                            traffic["seq"])(keys, 0)[0]["tokens"]
    params = weights.make(conf["init"], keys["weights"],
                          reference.layout(conf), jnp.float32)
    _, _, stats = jax.vmap(lambda t: reference._row_nll(
        conf, MM, params, t))(tokens)
    assert len(stats) == 2                          # two MoE layers
    assert sum(float(jnp.sum(s["dropped"])) for s in stats) >= 1


# ---------------------------------------------------------------------------
# the published options' layout
# ---------------------------------------------------------------------------

def test_lite_share_layout():
    lay = reference.layout(LITE_SHARE)
    att = {k.split("/", 3)[3]: v for k, v in lay.items()
           if k.startswith("prelude/p0_mla_dense/attn/")}
    assert att == {"wq": (2048, 16, 192), "kv_down": (2048, 512),
                   "kv_norm/scale": (512,), "k_rope": (2048, 64),
                   "k_up": (512, 16, 128), "v_up": (512, 16, 128),
                   "wo": (16, 128, 2048)}
    assert lay["blocks/b0_mla/ffn/router"] == (4, 2048, 64)
    assert lay["blocks/b0_mla/ffn/w_gate"] == (4, 8, 2048, 1408)
    assert lay["blocks/b0_mla/ffn/shared/down"] == (4, 2816, 2048)
    assert lay["prelude/p0_mla_dense/ffn/gate"] == (2048, 10944)
    assert [k for k in lay if k.startswith("final_ln/")] == ["final_ln/scale"]
    assert lay["embed/table"] == (12800, 2048)
    assert reference.rows_of("blocks/b0_mla/ffn/w_gate", (4, 8, 2048, 1408)) \
        == 4
    assert reference.rows_of("prelude/p0_mla_dense/ffn/gate",
                             (2048, 10944)) == 1
    # the share's count: MLA 13.76 M + dense 67.24 M + 4 x (MLA + router 0.13 M
    # + shared 17.30 M + 8 experts 69.21 M) + embedding 26.21 M, and norms
    mla = 2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 2 * 512 * 16 * 128 \
        + 16 * 128 * 2048
    assert mla == 13762560
    moe = 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    norms = 5 * (2 * 2048 + 512) + 2048
    assert sum(math.prod(s) for s in lay.values()) == (
        mla + 3 * 2048 * 10944 + 4 * (mla + moe) + 12800 * 2048 + norms)
    # FLOPs: a token uses 6 of the published 64 experts, whichever 8 are
    # held; MLA's Q.K over 128 + 64 and P.V over 128, (4096 + 1) / 2 keys
    used = 12800 * 2048 + 5 * mla + 3 * 2048 * 10944 + 4 * (
        2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408 * 6 // 64)
    assert flops.matmul_params(LITE_SHARE) == used == 257949696
    assert flops.per_token(LITE_SHARE, 4096) == (
        6 * used + 6 * 5 * 16 * (128 + 64 + 128) * 2048.5) == 1862347776
    # with q-LoRA the query latent gets its norm too
    q = reference.layout(dict(LITE_SHARE, q_lora_rank=1536))
    assert q["blocks/b0_mla/attn/q_down"] == (4, 2048, 1536)
    assert q["blocks/b0_mla/attn/q_norm/scale"] == (4, 1536)
    assert "blocks/b0_mla/attn/wq" not in q


@pytest.mark.parametrize("q_lora", [None, 12])
def test_published_options_run(q_lora):
    """q-LoRA off or on, latent norms and YaRN on: the reference's loss and
    gradient at a tiny width are finite and reach every leaf."""
    conf = small(q_lora_rank=q_lora, rope_scaling=LITE_SHARE["rope_scaling"],
                 seq_aux=True, experts_held=8, expert_offset=8,
                 init={"default": ["normal", 0.05],
                       "rules": [["scale$", "zeros"]]})
    shapes = reference.layout(conf)
    params = weights.make(conf["init"], jax.random.key(3), shapes,
                          jnp.float32)
    tokens = jax.random.randint(jax.random.key(4), (2, 16), 0, 64)
    loss, g = jax.value_and_grad(
        lambda p: reference.loss_fn(conf, p, tokens))(params)
    assert math.isfinite(float(loss))
    for k, v in g.items():
        assert bool(jnp.all(jnp.isfinite(v))), k
        assert float(jnp.max(jnp.abs(v))) > 0, k

