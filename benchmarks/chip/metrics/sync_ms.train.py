"""Device ms a round under the named scope ``sync``: the round's sync
(``core/engine.py``: ``round_step``): the average, delta, compression,
error feedback and staleness buffer, the broadcast-back, the momentum
average and the client drift. Read by ``scopes.read`` from the
traced window and the compiled step's text. Moves
``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "sync")
