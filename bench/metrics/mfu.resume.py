"""A whole resume's share of the chip's bf16 peak, in %: the training
FLOPs of the one step a resume runs (the reference module's
``train_flops_per_token``, batch × sequence tokens), over ``resume_s``
times the peak. It stands beside
``apply_unpack_roofline`` over the whole resume, so a kernel taken off the
restore's path still leaves a share that a claim must move."""


def read(run):
    from readers import value

    seconds = value(run, "resume_s")
    if seconds is None:
        return None
    flops = run.cfg["batch"] * run.cfg["seq"] \
        * run.model.train_flops_per_token(run.cfg)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
