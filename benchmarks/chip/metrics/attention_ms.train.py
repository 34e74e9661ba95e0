"""Device ms a round under the named scope ``attention``:
``models/layers.py:attention``, forward and backward, flash and Pallas
paths included; a part of ``model``. Read by ``scopes.read`` from the
traced window and the compiled step's text. Moves
``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "attention")
