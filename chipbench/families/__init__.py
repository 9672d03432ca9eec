"""One file per model family, ``families/<family>.py``, found by the
``family`` that a configuration file names. A family file gives all that
the benchmark needs of one architecture:

- ``PREFIX``: where the stacked blocks sit in the parameter tree;
- ``block(conf)``: ``{leaf path under PREFIX: shape of one layer}``;
- ``program_sizes(cfg)``: the configuration file's keys as the program's
  ``ModelConfig`` holds them, so that the two are checked to agree;
- ``MATMUL``: the block leaves that are matmul weights, for model FLOPs;
- ``mixing_flops(conf, seq)``: FLOPs per token of all layers that no
  weight counts (attention, a recurrence), forward and backward;
- ``final_norm_eps(conf)``: the epsilon of the last LayerNorm;
- ``layer(conf, mm, p, x)``: the reference's float32 layer on one row.

A new architecture is a new file here; nothing else changes."""
from __future__ import annotations

import importlib
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def get(name: str):
    """The module ``families/<name>.py``."""
    if not (HERE / f"{name}.py").is_file():
        have = sorted(p.stem for p in HERE.glob("*.py") if p.stem[0] != "_")
        raise ValueError(f"no family file chipbench/families/{name}.py "
                         f"(have {have})")
    return importlib.import_module(f"{__name__}.{name}")
