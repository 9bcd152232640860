"""The window's share of the chip's bf16 peak: tokens per second times
the model's training FLOPs per token (its reference module's
``train_flops_per_token``), over the peak, in %. Taken over the whole
window, so it still bounds a gain once a kernel has left the path."""


def read(run):
    from readers import value

    rate = value(run, "tokens_per_s")
    if rate is None:
        return None
    flops = run.model.train_flops_per_token(run.cfg)
    return 100.0 * rate * flops / run.peaks["bf16_flops_per_s"]
