"""Device bytes of the timed step as its compiler lays it out
(``compiled.memory_analysis()``): arguments + outputs + temporaries -
aliased. Guards the depth a chip holds. Layer: train step
(``repro/train/step.py``). Moves ``tokens_per_s``."""


def read(rec: dict):
    return rec.get("step_hbm_bytes") or None
