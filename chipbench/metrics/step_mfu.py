"""The whole train step's share of the chips' peak: model FLOPs per token
(``chipbench/flops.py``) times the tokens trained in the traced window,
over the window and the chips' bf16 peak. Layer: train step
(``repro/train/step.py``). Moves ``tokens_per_s``."""


def read(rec: dict):
    t = rec["trace"]
    if t["window_s"] <= 0 or not rec["tokens"]:
        return None
    achieved = rec["flops_per_token"] * rec["tokens"] / t["window_s"]
    return 100.0 * achieved / (rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
