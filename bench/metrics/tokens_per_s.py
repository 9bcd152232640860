"""Training goodput: every token trained in the window over the whole
window, each stall on the step loop included."""


def read(run):
    steps = [s for s in run.spans.within("step", *run.window)]
    if not steps:
        return None
    tokens = len(steps) * run.cfg["batch"] * run.cfg["seq"]
    return tokens / (run.window[1] - run.window[0])
