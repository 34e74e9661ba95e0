"""Cells cut to a size the CPU runs in seconds: the benchmark's own cells
with the program's reduced widths (``REDUCED`` of its configs), 2 layers
and 64-token sequences. Only the sizes differ from what the chip runs."""
import copy
import os

from benchmarks.chip import spec
from benchmarks.chip.traffic.generator import Mix

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
HERE = os.path.join(ROOT, "benchmarks", "chip")

# the program's reduced widths, per model type
SIZES = {
    "qwen2": dict(
        hidden_size=112, intermediate_size=224, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=512,
        program={"arch": "qwen2-0.5b", "reduced": True}),
    "mamba2": dict(
        d_model=128, d_state=16, headdim=32, chunk_size=32, vocab_size=512,
        program={"arch": "mamba2-1.3b", "reduced": True}),
}
CELLS = {"qwen2": "train-qwen2-0.5b-m2h2-s512",
         "mamba2": "train-mamba2-1.3b-m2h2-s4096"}


def cell(kind: str, seq_len: int = 64, layers: int = 2, clients: int = None):
    """The cell of ``kind`` (``qwen2``, ``mamba2``), its method and limits
    as committed, at the tiny size; with ``clients``, that many clients
    on as many chips, one client a chip."""
    c = spec.load_cell(ROOT, CELLS[kind])
    depth = spec.model_type(kind).DEPTH
    c.config = dict(c.config, **copy.deepcopy(SIZES[kind]),
                    **{depth: layers})
    if clients:
        c.chips = clients
    c.mix = Mix(clients=clients or c.mix.clients,
                local_steps=c.mix.local_steps,
                batch=c.mix.batch, seq_len=seq_len, source=c.mix.source,
                n_chains=c.mix.n_chains, branching=c.mix.branching)
    return c
