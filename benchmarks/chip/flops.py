"""Model FLOPs of a training token, from a configuration file's shapes.

A multiply-add is 2 FLOPs. Training counts the forward pass and a backward
pass of twice its work, 3x the forward FLOPs, for every matmul-form term.
Not counted: recomputation (remat), the optimizer's elementwise work,
norms, activations, softmax and other elementwise operations, and the
embedding gather. Every term is per token, at sequence length S. The
forward FLOPs of each model type, and how they are counted, are its
``model_types/<model_type>.py``'s ``forward_flops``.
"""
from __future__ import annotations

from benchmarks.chip import spec


def train_flops_per_token(cfg: dict, S: int) -> float:
    return 3.0 * spec.model_type(cfg["model_type"]).forward_flops(cfg, S)
