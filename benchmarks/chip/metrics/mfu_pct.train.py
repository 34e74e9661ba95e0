"""Model FLOP utilization of the whole round step, in %: the model FLOPs
of a training token (``flops.train_flops_per_token``) times the run's
untraced ``train_tokens_per_s``, over the cell's chips times the chip's
bf16 peak (``peaks.json``). Moves ``train_tokens_per_s``."""
from benchmarks.chip import flops


def read(ctx):
    per_token = flops.train_flops_per_token(ctx.config, ctx.seq_len)
    return 100.0 * per_token * ctx.tokens_per_s / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
