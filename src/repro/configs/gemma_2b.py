"""gemma-2b [dense]: 18L d_model=2048 8H (MQA kv=1) head_dim=256 d_ff=16384
vocab=256000 — GeGLU, embed scaling, full global attention. [arXiv:2403.08295]

Sharding notes: 8 query heads and 1 kv head cannot split over a 16-way model
axis, so tensor parallelism lands on head_dim (256) instead."""
import dataclasses

import jax.numpy as jnp

from repro.configs.registry import ArchSpec
from repro.models.transformer import ModelConfig

FULL = ModelConfig(
    name="gemma-2b", vocab=256_000, d_model=2048,
    pattern=("attn_full",), num_periods=18,
    num_heads=8, num_kv_heads=1, head_dim=256,
    d_ff=16384, mlp_kind="gated", act="gelu",
    norm="rms", embed_scale=True, rope_theta=10_000.0,
    remat="full", dtype=jnp.bfloat16,
)

SMOKE = ModelConfig(
    name="gemma-2b-smoke", vocab=512, d_model=256,
    pattern=("attn_full",), num_periods=2,
    num_heads=4, num_kv_heads=1, head_dim=64,
    d_ff=512, mlp_kind="gated", act="gelu",
    norm="rms", embed_scale=True, remat="none", dtype=jnp.float32,
)

# One v5e chip (16 GB) at the published widths. Training the whole model
# costs 14 B a parameter (bf16 params, grads and EF residual; f32 Adam m and
# v): 2.5 B parameters, ~35 GB. So depth and this chip's share of the
# vocabulary are cut, and no width: CHIP_LAYERS layers of 110.1 M parameters
# and 64,000 of the 256,000 embedding rows (131 M).
CHIP_LAYERS = 2
CHIP = dataclasses.replace(FULL, name="gemma-2b-chip",
                           num_periods=CHIP_LAYERS, vocab=64_000)
REDUCED = {
    "num_periods": f"{CHIP_LAYERS} of 18 layers; the other layers would lie "
                   "on further chips as pipeline stages",
    "vocab": "64,000 of 256,000 rows: this chip's share of a vocabulary "
             "split over 4 chips (ids, logits and loss over the slice)",
}

RULES = {"heads": None, "kv_heads": None, "head_dim": "model"}


def spec() -> ArchSpec:
    return ArchSpec(
        arch_id="gemma-2b", source="arXiv:2403.08295",
        model=FULL, smoke=SMOKE, chip=CHIP, reduced=REDUCED,
        shapes=("train_4k", "prefill_32k", "decode_32k"),
        skip_notes={"long_500k": "gemma-1 has full global attention only; no "
                                 "sliding-window/sub-quadratic variant exists "
                                 "in the source model."},
        rules_overrides=RULES,
    )
