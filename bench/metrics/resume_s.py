"""Restart to the first resumed step: a fresh ``Trainer`` over the run
directory (which restores) to the end of its first step, over every
resume in the window."""


def read(run):
    ops = run.spans.within("resume", *run.window)
    if not ops:
        return None
    return sum(s.seconds for s in ops) / len(ops)
