"""Seconds per save that ``AsyncFlusher.submit`` blocked on a full queue:
each submit's time less its staging copy, over the window's submits."""


def read(run):
    lo, hi = run.window
    subs = run.spans.within("submit", lo, hi)
    if not subs:
        return None
    stage = sum(s.seconds for s in run.spans.within("stage", lo, hi))
    return (sum(s.seconds for s in subs) - stage) / len(subs)
