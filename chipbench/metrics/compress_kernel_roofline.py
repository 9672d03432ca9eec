"""The Pallas compression kernels' share of their roofline: the least time
the chip needs for the work, one bfloat16 read of every coordinate the
sparse groups compress at peak HBM bandwidth (the work is bound by bytes:
a handful of operations per coordinate), over the device time of all
Pallas custom calls per step and chip. Counts the work, not the
implementation, so a fused or replaced selection can approach 100% but
not pass it. Layer: kernels (``repro/kernels/sparsify/kernel.py``).
Moves ``tokens_per_s``."""


def read(rec: dict):
    t = rec["trace"]
    per_step = t["pallas_s"] / t["devices"] / rec["steps"] if rec["steps"] else 0
    if per_step <= 0:
        return None
    least = rec["compress_coords"] * 2 / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / per_step
