"""Architecture registry: every assigned arch exposes spec() -> ArchSpec with
the exact full-size config, a reduced smoke variant, per-arch sharding-rule
overrides, and input-shape applicability."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any

from repro.models.transformer import ModelConfig

# Input shapes assigned to this paper (seq_len, global_batch, kind)
SHAPES: dict[str, tuple[int, int, str]] = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}

ARCHS = [
    "gemma2_9b", "gemma_2b", "paligemma_3b", "seamless_m4t_large_v2",
    "starcoder2_7b", "phi35_moe", "deepseek_v2", "rwkv6_1b6",
    "zamba2_2b7", "gemma2_27b", "deepseek_v2_lite",
]

# canonical ids as assigned (hyphens) -> module names
ID_TO_MODULE = {
    "gemma2-9b": "gemma2_9b",
    "gemma-2b": "gemma_2b",
    "paligemma-3b": "paligemma_3b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "starcoder2-7b": "starcoder2_7b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "deepseek-v2-236b": "deepseek_v2",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "rwkv6-1.6b": "rwkv6_1b6",
    "zamba2-2.7b": "zamba2_2b7",
    "gemma2-27b": "gemma2_27b",
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    arch_id: str                      # canonical assigned id
    source: str                       # paper / model-card citation
    model: ModelConfig                # full-size config (dry-run only)
    smoke: ModelConfig                # reduced variant (CPU-runnable)
    shapes: tuple[str, ...]           # applicable input-shape names
    skip_notes: dict[str, str]        # shape -> why skipped
    rules_overrides: dict[str, Any] = dataclasses.field(default_factory=dict)
    train_mode: str = "compressed"    # compressed (Alg.1) | fsdp (+step-7 Q)
    notes: str = ""
    chip: ModelConfig | None = None   # one chip's share at published widths
    reduced: dict[str, str] = dataclasses.field(default_factory=dict)
                                      # chip config: each cut -> what it
                                      # stands for in the deployment

    def batch_inputs(self, shape_name: str) -> dict:
        """Extra (non-token) model inputs per shape, as (shape, dtype) specs.
        Populated by configs that need stub frontends."""
        return {}


def get(arch: str) -> ArchSpec:
    mod_name = ID_TO_MODULE.get(arch, arch.replace("-", "_").replace(".", ""))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.spec()


def all_specs() -> dict[str, ArchSpec]:
    return {name: importlib.import_module(f"repro.configs.{name}").spec()
            for name in ARCHS}
