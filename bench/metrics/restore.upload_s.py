"""Seconds per resume uploading the restored state to the device: self
time of ``trainer.upload``, over the resumes whose build lies wholly inside
the traced window."""

import phases


def read(run):
    return phases.per_resume(run, phases.self_seconds("trainer.upload"))
