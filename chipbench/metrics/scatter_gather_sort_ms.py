"""Device milliseconds per step and chip of the XLA operations that run a
scatter, gather or sort, alone or fused with other operations: the
compaction of the emit ops, the Rice codec and the apply of the received
buffers. Layer: emit ops and Rice codec (``repro/kernels/sparsify/ops.py``,
``repro/comm/compaction.py``, the apply in ``repro/comm/sync.py``). Moves
``tokens_per_s``."""

OPCODES = {"scatter", "gather", "sort"}


def read(rec: dict):
    t = rec["trace"]
    secs = sum(op["seconds"] for op in t["ops"].values()
               if OPCODES & set(op["runs"]))
    if secs <= 0 or not rec["steps"]:
        return None
    return 1000.0 * secs / t["devices"] / rec["steps"]
