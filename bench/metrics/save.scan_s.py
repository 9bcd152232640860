"""Seconds per save in the scan: self time of ``ckpt.save.scan`` (each
leaf's upload with its snapshot, ``flush_pack`` or the staged chain, and the
fetch of its dirty block ids and checksums), over the saves wholly inside
the traced window."""

import phases


def read(run):
    return phases.per_save(run, phases.self_seconds("ckpt.save.scan"))
