"""Device ms a round under the named scope ``ssd_scan``:
``models/ssm.py:ssd_chunked``, the chunked SSD scan, forward and
backward; a part of ``model``. Read by ``scopes.read`` from the
traced window and the compiled step's text. Moves
``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "ssd_scan")
