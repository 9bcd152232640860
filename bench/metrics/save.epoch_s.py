"""Seconds per save in the write-back epoch into the (simulated) PMem:
self time of ``ckpt.save.epoch``, over the saves wholly inside the traced
window."""

import phases


def read(run):
    return phases.per_save(run, phases.self_seconds("ckpt.save.epoch"))
