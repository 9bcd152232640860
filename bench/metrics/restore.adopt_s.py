"""Seconds per resume adopting the restored checkpoint: self time of
``ckpt.restore.adopt`` (reopening the pages, filling the cache frames),
over the resumes whose build lies wholly inside the traced window."""

import phases


def read(run):
    return phases.per_resume(run, phases.self_seconds("ckpt.restore.adopt"))
