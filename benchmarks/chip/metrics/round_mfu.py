"""Model FLOP/s utilisation of the whole round, in percent of the chip's
bf16 peak: the forward and backward FLOPs of every sampled client's batch
(3 forward passes' worth, from the configuration family's FLOP count) over
the untraced window's mean round time."""


def read(ctx):
    seconds = ctx.round_ms * 1e-3
    return 100.0 * ctx.flops_per_round / (seconds * ctx.peak["bf16_flops_per_s"])
