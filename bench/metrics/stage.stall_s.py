"""Seconds per save that the step loop spends staging the state to the
host: ``Trainer._ckpt_state`` (device to host) plus
``AsyncFlusher.stage`` (the host copy), over the window's submits."""


def read(run):
    subs = run.spans.within("submit", *run.window)
    if not subs:
        return None
    lo, hi = run.window
    total = sum(s.seconds for s in run.spans.within("ckpt_state", lo, hi))
    total += sum(s.seconds for s in run.spans.within("stage", lo, hi))
    return total / len(subs)
