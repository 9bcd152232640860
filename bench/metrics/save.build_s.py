"""Seconds per save building pages: self time of ``ckpt.save.build`` (each
leaf's pages and checksums into the buffer manager), over the saves wholly
inside the traced window."""

import phases


def read(run):
    return phases.per_save(run, phases.self_seconds("ckpt.save.build"))
