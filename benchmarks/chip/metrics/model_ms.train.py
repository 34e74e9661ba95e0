"""Device ms a round under the named scope ``model``: the loss-and-gradient
call of each local step (``core/engine.py``:
``_client_loop.local_step_one_client``, ``_fused_run.scan_body``): the
model's forward and backward. Read by ``scopes.read`` from the
traced window and the compiled step's text. Moves
``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "model")
