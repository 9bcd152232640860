"""Seconds per resume in the restore scan: self time of
``ckpt.restore.scan`` (each leaf's header checks, page gather, upload,
``apply_unpack`` or the staged chain, and the fetch of the image), over the
resumes whose build lies wholly inside the traced window."""

import phases


def read(run):
    return phases.per_resume(run, phases.self_seconds("ckpt.restore.scan"))
