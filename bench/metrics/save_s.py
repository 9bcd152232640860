"""Time to make one checkpoint durable: the manager's ``save`` from its
start to its return after the manifest commit and fsync, over every save
that completed in the window."""


def read(run):
    lo, hi = run.window
    saves = [s for s in run.spans.items
             if s.name == "save" and lo <= s.t1 <= hi]
    if not saves:
        return None
    return sum(s.seconds for s in saves) / len(saves)
