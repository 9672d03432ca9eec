"""One file per model family, ``families/<family>.py``, found by the
``family`` that a configuration file names. A family file gives all that
the benchmark needs of one architecture:

- ``PERIOD``: the kinds of one period, each stacked under
  ``blocks/b<j>_<kind>/`` with a leading layer axis, as many periods deep
  as the layers left after the prelude fill; and where the model has
  leading blocks, ``prelude(conf)``: their kinds, each unstacked under
  ``prelude/p<j>_<kind>/``. The forward pass runs the prelude, then each
  period's kinds in order, as ``repro/models/transformer.py`` does;
- ``block(conf, kind)``: ``{leaf path inside the block: shape of one
  layer}``;
- ``final_norm_eps(conf)``: the epsilon of the final LayerNorm (scale and
  bias under ``final_ln/``); or, for another final norm,
  ``final_norm(conf)``: ``{leaf under final_ln/: shape}``, and
  ``final_norm_apply(conf, p, x)``;
- ``layer(conf, mm, kind, p, x)``: the reference's float32 layer on one
  row; it returns ``x``, or ``(x, stats)`` where the layer routes;
- ``router_loss(conf, stats)``: only where layers return statistics: the
  term added to the token-mean NLL, from the list of every routing
  layer's statistics, each with a leading batch axis;
- ``program_sizes(cfg)``: the configuration file's keys as the program's
  ``ModelConfig`` holds them, so that the two are checked to agree;
- ``MATMUL``: the block leaves that are matmul weights, for model FLOPs;
  ``ROUTED`` those of them that are routed experts, and
  ``routed_share(conf)`` the share of a routed weight one token uses;
- ``mixing_flops(conf, seq)``: FLOPs per token of all layers that no
  weight counts (attention, a recurrence), forward and backward.

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
