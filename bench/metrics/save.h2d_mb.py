"""Megabytes (1e6 B) a save uploads to the device: ``h2d_bytes`` of the
program's ``ckpt.save`` span (each leaf, and its snapshot in a delta save),
over the saves wholly inside the traced window."""

import phases


def read(run):
    return phases.per_save(run, phases.megabytes("h2d_bytes", "ckpt.save"))
