"""Seconds per save rebuilding each leaf's last-flushed snapshot from
the cache frames: self time of the program's ``ckpt.save.snapshot`` spans,
over the saves wholly inside the traced window."""

import phases


def read(run):
    return phases.per_save(run, phases.self_seconds("ckpt.save.snapshot"))
