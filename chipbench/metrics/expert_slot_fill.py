"""The share of the held experts' slot rows that hold a routed choice: the
routed choices this chip's experts compute over the rows their matmuls
compute, mean over the MoE layers (the program's counter
``expert_slot_fill`` in the step's metrics, in %), mean over the traced
window's steps. A program without the counter, or a model without MoE
layers, reads nothing. Layer: moe (``repro/models/moe.py``). Moves
``tokens_per_s``."""
import statistics


def read(rec: dict):
    steps = rec["per_step"]
    if not steps or any("expert_slot_fill" not in m for m in steps):
        return None
    return statistics.fmean(m["expert_slot_fill"] for m in steps)
