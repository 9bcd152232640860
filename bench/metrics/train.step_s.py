"""Mean wall time of the window's steps that submit no save."""


def read(run):
    k = run.traffic["ckpt_every"]
    steps = [s for s in run.spans.within("step", *run.window)
             if (s.step + 1) % k]
    if not steps:
        return None
    return sum(s.seconds for s in steps) / len(steps)
