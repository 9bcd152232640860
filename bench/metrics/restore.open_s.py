"""Seconds per resume opening the checkpoint: self time of
``ckpt.restore.open`` (pool open, manifest recovery, layout, durable
view), over the resumes whose build lies wholly inside the traced window."""

import phases


def read(run):
    return phases.per_resume(run, phases.self_seconds("ckpt.restore.open"))
