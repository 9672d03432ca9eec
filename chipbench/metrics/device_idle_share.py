"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the chips.
Layer: device. Moves ``tokens_per_s``."""


def read(rec: dict):
    t = rec["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
