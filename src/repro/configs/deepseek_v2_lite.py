"""deepseek-v2-lite [moe]: 27L d_model=2048 16H MLA without q-LoRA
(kv_lora=512 with an RMSNorm on the latent, qk_nope=128, qk_rope=64, v=128,
YaRN factor 40 over 4096 original positions), layer 0 dense SwiGLU FFN
(10944), layers 1-26 DeepSeekMoE: 64 routed experts (d_expert=1408), greedy
softmax top-6 without renormalisation + 2 shared experts, per-row balance
term (seq_aux, alpha 0.001), no token dropping, vocab=102400.
[huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json, arXiv:2405.04434]

The registry holds every routed expert; a chip's share of an
expert-parallel layer sets ``moe.experts_held`` / ``moe.expert_offset``
(the benchmark's ``deepseek-v2-lite-chip`` holds 8 of 64)."""
import jax.numpy as jnp

from repro.configs.registry import ArchSpec
from repro.models.moe import MoEConfig
from repro.models.transformer import ModelConfig

YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
        ("mscale", 0.707), ("mscale_all_dim", 0.707),
        ("original_max_position_embeddings", 4096), ("type", "yarn"))

MOE = MoEConfig(d_model=2048, d_expert=1408, num_experts=64, top_k=6,
                num_shared=2, capacity_factor=None, act="silu",
                normalize_weights=False, routed_scaling_factor=1.0,
                seq_aux=True, aux_loss_coef=0.001, z_loss_coef=0.0)

FULL = ModelConfig(
    name="deepseek-v2-lite", vocab=102_400, d_model=2048,
    prelude=("mla_dense",), pattern=("mla",), num_periods=26,   # 27 layers
    num_heads=16, num_kv_heads=16, first_dense_ff=10944,
    mla_kv_lora=512, mla_q_lora=0, mla_qk_nope=128, mla_qk_rope=64,
    mla_v=128, mla_latent_norm=True,
    rope_theta=10_000.0, rope_scaling=YARN, norm="rms",
    act="silu", moe=MOE, remat="full", dtype=jnp.bfloat16,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke", vocab=512, d_model=128,
    prelude=("mla_dense",), pattern=("mla",), num_periods=1,    # 2 layers
    num_heads=4, num_kv_heads=4, first_dense_ff=256,
    mla_kv_lora=32, mla_q_lora=0, mla_qk_nope=16, mla_qk_rope=8, mla_v=16,
    mla_latent_norm=True, rope_scaling=YARN, norm="rms", act="silu",
    moe=MoEConfig(d_model=128, d_expert=64, num_experts=4, top_k=2,
                  num_shared=1, capacity_factor=None, act="silu",
                  normalize_weights=False, seq_aux=True,
                  aux_loss_coef=0.001, z_loss_coef=0.0),
    remat="none", dtype=jnp.float32,
)

RULES = {"kv_lora": None, "qk_rope": None}


def spec() -> ArchSpec:
    return ArchSpec(
        arch_id="deepseek-v2-lite",
        source="huggingface.co/deepseek-ai/DeepSeek-V2-Lite (config.json); "
               "arXiv:2405.04434",
        model=FULL, smoke=SMOKE,
        shapes=("train_4k", "prefill_32k", "decode_32k"),
        skip_notes={"long_500k": "full attention: a 500k prefill is "
                                 "quadratic, past the 163840 positions "
                                 "the config declares."},
        rules_overrides=RULES,
    )
