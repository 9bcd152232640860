"""Seconds per save committing: self time of ``ckpt.save.commit`` (the
manifest records, the append and the fsync), over the saves wholly inside
the traced window."""

import phases


def read(run):
    return phases.per_save(run, phases.self_seconds("ckpt.save.commit"))
