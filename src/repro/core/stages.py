"""The stages of the compressed train step, named inside the program.

Each stage's work is traced under ``jax.named_scope("stage.<name>")``, so
every HLO instruction it lowers to carries the name in its ``op_name``
metadata (``jit(step)/.../stage.apply/scatter-add``; the backward pass as
``transpose(jvp(stage.model))/...``). A profiler trace names its device
events by instruction, so each event's device time can be put under the
stage that issued it. Scopes nest: the innermost stage names the
instruction (``compact`` runs inside ``compress``). A scope changes only
metadata: the compiled program is the same with or without it.

  model      forward and backward pass
  compress   uniform draws, selection kernels, lambda, error-feedback residual
  compact    the compact write of the selected coordinates and its codec encode
  pack       wire layout encode: bitmap / Golomb-Rice words, coordinate order
  exchange   the collectives
  decode     value decode and wire layout decode of the gathered buffers
  apply      scatter-add of the gathered buffers into the averaged gradient
  optimizer  the optimizer update

Inside ``model``, a mixture-of-experts layer (``repro.models.moe``) names
its own (``MOE_STAGES``):

  route      router logits, top-k, the balance term
  dispatch   ordering choices by expert and gathering them into the held
             experts' slot rows
  experts    the held and shared experts' matmuls
  combine    gathering the slot outputs back, weighting, summing
"""
from __future__ import annotations

import jax

MOE_STAGES = ("route", "dispatch", "experts", "combine")
STAGES = ("model", "compress", "compact", "pack", "exchange", "decode",
          "apply", "optimizer") + MOE_STAGES


def stage(name: str):
    """``jax.named_scope`` of one stage of ``STAGES``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; have {STAGES}")
    return jax.named_scope("stage." + name)
