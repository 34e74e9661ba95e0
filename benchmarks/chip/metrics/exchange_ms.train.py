"""Device ms a round under the named scope ``exchange``: the cross-client
average of ``core/engine.py``'s ``make_sync``, inside ``sync`` (the
params' and momentum's mean) and ``server`` (the last gradients' mean).
On a mesh, one client a chip, the partitioner's all-reduces carry it, so
this is the exchange's whole device time, hidden by compute or not
(``collective_exposed_ms.train`` is the part not hidden). Read by
``scopes.read`` from the traced window and the compiled step's text;
None where the program names no such scope. Moves
``train_tokens_per_s``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.read(ctx, "exchange")
