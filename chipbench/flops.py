"""Model FLOPs of one trained token, from the configuration's sizes.

Forward and backward of a matmul cost 6 FLOPs per weight per token (2
forward, 4 backward). Counted: every matmul weight of every layer, and the
tied unembedding (vocab x hidden); not the embedding lookup, norms,
biases or elementwise mixes. The family file adds what no weight counts
(``mixing_flops``: attention, a recurrence). Recomputation under remat is
not counted."""
from __future__ import annotations

import math

from chipbench import families, reference


def matmul_params(conf: dict) -> int:
    fam = families.get(conf["family"])
    shapes = reference.layout(conf)
    n = math.prod(shapes["embed/table"])            # tied unembedding
    return n + sum(math.prod(shapes[fam.PREFIX + k])  # (layers, ...) stacked
                   for k in fam.MATMUL)


def per_token(conf: dict, seq: int) -> float:
    fam = families.get(conf["family"])
    return 6.0 * matmul_params(conf) + fam.mixing_flops(conf, seq)
