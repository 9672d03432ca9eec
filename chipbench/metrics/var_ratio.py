"""The step's variance ratio ``||Q(g)||^2 / ||g||^2`` (the paper's
``var``, size-weighted over the compressed leaves): the program's own
count in the step's metrics, mean over the traced window's steps. A step
that gets faster with a noisier gradient shows here. Layer: sync
(``repro/comm/sync.py``). Moves ``tokens_per_s``."""
import statistics


def read(rec: dict):
    steps = rec["per_step"]
    if not steps:
        return None
    return statistics.fmean(m["var_ratio"] for m in steps)
