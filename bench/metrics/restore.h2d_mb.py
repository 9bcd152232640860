"""Megabytes (1e6 B) a resume uploads to the device: ``h2d_bytes`` of the
program's ``ckpt.restore`` (packed pages, zero base, block ids,
checksums) plus ``trainer.upload`` (the restored state), over the resumes
whose build lies wholly inside the traced window."""

import phases


def read(run):
    return phases.per_resume(
        run, phases.megabytes("h2d_bytes", "ckpt.restore", "trainer.upload"))
