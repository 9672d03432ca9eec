"""Bytes of one worker's message per step: the program's own count
(``SyncStats.wire_bytes`` in the step's metrics), mean over the traced
window's steps. The paper's coding length: a step that gets faster by
shipping more bytes shows here. Layer: sync (``repro/comm/sync.py``).
Moves ``tokens_per_s``."""
import statistics


def read(rec: dict):
    steps = rec["per_step"]
    if not steps:
        return None
    return statistics.fmean(m["wire_bytes"] for m in steps)
