"""Device ms a round under the named scope ``local_step``: the rest of each
local step (``core/engine.py``): the clip, the D statistic and update
under local scaling, and the scaled momentum step (``_apply_update``, or
the fused kernel). Read by ``scopes.read`` from the traced window
and the compiled step's text. Moves ``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "local_step")
