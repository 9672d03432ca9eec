"""Model FLOPs of one trained token, from the configuration's sizes.

Forward and backward of a matmul cost 6 FLOPs per weight per token (2
forward, 4 backward). Counted: every matmul weight of every layer that a
token uses, and the tied unembedding (vocab x hidden); not the embedding
lookup, norms, biases or elementwise mixes. A routed expert's weight
(the family's ``ROUTED``) counts at the share of itself that one token
uses on average, ``routed_share(conf)``: top_k over the published number
of routed experts, whichever experts this chip holds. The family file
adds what no weight counts (``mixing_flops``: attention, a recurrence).
Recomputation under remat is not counted."""
from __future__ import annotations

import math

from chipbench import families, reference


def matmul_params(conf: dict):
    """Matmul weights one token uses: an int, or a ``Fraction`` where a
    routed share applies."""
    fam = families.get(conf["family"])
    routed = getattr(fam, "ROUTED", ())
    shapes = reference.layout(conf)
    n = math.prod(shapes["embed/table"])            # tied unembedding
    for path, shape in shapes.items():
        leaf = reference.block_leaf(path)
        if leaf in fam.MATMUL:                       # (layers, ...) stacked
            size = math.prod(shape)
            n += size * fam.routed_share(conf) if leaf in routed else size
    return n


def per_token(conf: dict, seq: int) -> float:
    fam = families.get(conf["family"])
    return float(6 * matmul_params(conf)) + fam.mixing_flops(conf, seq)
