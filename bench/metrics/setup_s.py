"""Set-up: from the start of the process to the opening of the window —
imports, JAX's start, the program's build, weights, compiles (from the
cache after a checkout's first run) and the warm-up the traffic asks for."""


def read(run):
    return run.setup_s
