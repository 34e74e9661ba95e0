"""Device ms a round under the named scope ``server``: the server side of
the round (``core/engine.py``: ``round_step``): the D update at sync
(Algorithm 1 line 4), the loss metrics, the controller and the server
update. Read by ``scopes.read`` from the traced window and the
compiled step's text. Moves ``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "server")
