"""Share of the traced window in which no operation ran on a chip, in %,
averaged over the cell's chips (``trace.busy_s`` over ``trace.window_s``).
Moves ``train_tokens_per_s``: a round the host holds up is a round the
chips wait for."""
from benchmarks.chip import trace


def read(ctx):
    if ctx.events is None:
        return None
    busy = trace.busy_s(ctx.events)
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace.window_s(ctx.events))
