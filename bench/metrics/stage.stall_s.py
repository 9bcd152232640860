"""Seconds per save that the step loop spends staging the state to the
host: ``Trainer._ckpt_state`` (device to host) plus, with a flusher,
``AsyncFlusher.stage`` (the host copy), over the window's stagings (one a
save, async or sync)."""


def read(run):
    lo, hi = run.window
    stagings = run.spans.within("ckpt_state", lo, hi)
    if not stagings:
        return None
    total = sum(s.seconds for s in stagings)
    total += sum(s.seconds for s in run.spans.within("stage", lo, hi))
    return total / len(stagings)
