"""The one generator of training traffic: federated rounds of synthetic
token sequences, each mix described by a data file in ``mixes/``.

A mix file gives the round's shape (``clients`` M, ``local_steps`` H,
``batch`` b per client step, ``seq_len`` S) and the token source. The only
source so far is ``markov``: tokens walk one of ``n_chains`` order-1 Markov
chains over the vocabulary, each token having ``branching`` possible
successors, chosen uniformly. This is a copy of the program's
``data/synthetic.TokenStream``: kept here so that the benchmark's inputs do
not move when the program's data code does.

Round r of seed s is a pure function of (s, r, the mix, the vocabulary):
every round draws fresh rows, and the same seed gives the same rounds.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

MIX_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "mixes")


@dataclasses.dataclass(frozen=True)
class Mix:
    clients: int
    local_steps: int
    batch: int
    seq_len: int
    source: str
    n_chains: int
    branching: int

    @property
    def tokens_per_round(self) -> int:
        return self.clients * self.local_steps * self.batch * self.seq_len


def load_mix(name: str, mix_dir: str = MIX_DIR) -> Mix:
    with open(os.path.join(mix_dir, f"{name}.json")) as f:
        raw = json.load(f)
    fields = {f.name for f in dataclasses.fields(Mix)}
    unknown = set(raw) - fields - {"why"}
    if unknown:
        raise ValueError(f"traffic mix {name}: unknown keys {sorted(unknown)}")
    mix = Mix(**{k: raw[k] for k in fields})
    if mix.source != "markov":
        raise ValueError(f"traffic mix {name}: unknown source {mix.source!r}")
    for k in ("clients", "local_steps", "batch", "seq_len", "n_chains",
              "branching"):
        if int(getattr(mix, k)) < 1:
            raise ValueError(f"traffic mix {name}: {k} must be >= 1")
    return mix


class RoundTraffic:
    """``round(r)`` -> {"tokens", "labels"}: int32 arrays (M, H, b, S);
    labels are the next tokens."""

    def __init__(self, mix: Mix, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab = vocab_size
        self.seed = int(seed)
        rng = np.random.default_rng((self.seed, 0))
        self.chains = rng.integers(0, vocab_size,
                                   size=(mix.n_chains, vocab_size,
                                         mix.branching), dtype=np.int32)

    def _walk(self, rng, rows: int, seq_len: int):
        cid = rng.integers(self.chains.shape[0], size=rows)
        out = np.empty((rows, seq_len + 1), dtype=np.int32)
        out[:, 0] = rng.integers(self.vocab, size=rows)
        branch = rng.integers(self.chains.shape[2], size=(rows, seq_len))
        for s in range(seq_len):
            out[:, s + 1] = self.chains[cid, out[:, s], branch[:, s]]
        return out

    def round(self, r: int) -> dict:
        m = self.mix
        rng = np.random.default_rng((self.seed, 1, int(r)))
        walk = self._walk(rng, m.clients * m.local_steps * m.batch, m.seq_len)
        shape = (m.clients, m.local_steps, m.batch, m.seq_len)
        return {"tokens": walk[:, :-1].reshape(shape),
                "labels": walk[:, 1:].reshape(shape)}
