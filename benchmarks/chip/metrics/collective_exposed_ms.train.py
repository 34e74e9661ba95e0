"""Device ms a round in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, or an asynchronous one
from its start to its done) was under way on a chip and no other
operation ran there, averaged over the cell's chips
(``trace.exposed_collective_s`` over ``trace.rounds``): the sync's
exchange across chips that compute does not hide. None where no chip
ran a collective in the traced window, or no round started in it.
Moves ``train_tokens_per_s``."""
from benchmarks.chip import trace


def read(ctx):
    if ctx.events is None:
        return None
    per_chip = trace.exposed_collective_s(ctx.events)
    n = trace.rounds(ctx.events)
    if not n or all(s is None for s in per_chip):
        return None
    return 1e3 * sum(s or 0.0 for s in per_chip) / len(per_chip) / n
