"""Seconds per resume to build the ``Trainer``: open the pools, restore
the checkpoint and upload the state to the device."""


def read(run):
    builds = run.spans.within("build", *run.window)
    if not builds:
        return None
    return sum(s.seconds for s in builds) / len(builds)
