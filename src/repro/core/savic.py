"""SAVIC — Algorithm 1: Local SGD with preconditioning via scaling.

A *round* = H local steps on each of M clients followed by one synchronization
(parameter averaging) — the H-th step is the averaged one, exactly matching
Algorithm 1's sync timestep. The preconditioner D̂ is updated only at sync and
is identical on every client (*global scaling*, the analyzed setting); the
experimental *local scaling* variant (per-client D updated every local step)
is also implemented.

Since the round-engine refactor this module is a thin method definition over
``core/engine.py``: SAVIC = locally-scaled heavy-ball ClientLoop × weighted /
quantized SyncStrategy × identity-averaging ServerUpdate. The engine emits the
exact program the pre-refactor monolith did (regression-pinned in
tests/test_engine.py); the state pytree and public API are unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from repro.core import engine
from repro.core.preconditioner import PrecondConfig


@dataclasses.dataclass(frozen=True)
class SavicConfig:
    gamma: float = 0.1                 # step size γ
    beta1: float = 0.9                 # heavy-ball momentum (paper's exps: 0.9)
    scaling: str = "global"            # "global" (Algorithm 1) | "local"
    # D-stat at sync: "avg_grad" (H from the client-averaged sync gradient) |
    # "avg_local" (average of per-client stats)
    stat_source: str = "avg_grad"
    average_momentum: bool = True      # average momentum buffers at sync
    weight_decay: float = 0.0
    grad_clip: float = 0.0             # global-norm clip per local step (0=off)
    # sync compression (beyond-paper; cf. the quantization line of related
    # work [19,20]): all-reduce params/momentum in this dtype ("" = full)
    sync_dtype: str = ""
    # partial participation (beyond-paper; the compared Algorithm 2 of [42]
    # samples a client subset per round): fraction of clients whose updates
    # enter the sync average; non-participants keep local state but are
    # overwritten by the average (cross-device FedAvg semantics). 1.0 = all.
    participation: float = 1.0
    # sync delta compression (topk/randk/int8-stochastic, optional EF
    # residual; engine SyncStrategy layer, DESIGN.md §4)
    compression: engine.CompressionSpec = engine.CompressionSpec()
    # systems heterogeneity: per-client local-step vector H_m (None = uniform;
    # engine ClientLoop masking, DESIGN.md §5)
    local_steps: tuple = None
    # staleness-buffered server (FedBuff-style delta FIFO, DESIGN.md §5)
    asynchrony: engine.AsyncSpec = engine.AsyncSpec()


def engine_spec(pc_cfg: PrecondConfig, sv_cfg: SavicConfig) -> engine.EngineSpec:
    """SavicConfig × PrecondConfig -> the engine's three-layer spec."""
    return engine.EngineSpec(
        client=engine.ClientLoopSpec(
            lr=sv_cfg.gamma, momentum=sv_cfg.beta1, scaling=sv_cfg.scaling,
            stat_source=sv_cfg.stat_source, weight_decay=sv_cfg.weight_decay,
            grad_clip=sv_cfg.grad_clip,
            local_steps=sv_cfg.local_steps),
        sync=engine.SyncSpec(
            participation=sv_cfg.participation, sync_dtype=sv_cfg.sync_dtype,
            average_momentum=sv_cfg.average_momentum,
            compression=sv_cfg.compression,
            asynchrony=sv_cfg.asynchrony),
        server=engine.ServerSpec(kind="average"),
        precond=pc_cfg)


def init_state(key, init_params_fn, pc_cfg: PrecondConfig, sv_cfg: SavicConfig,
               n_clients: int):
    """Build the SAVIC train state. x_0^m = x_0 (identical start, Algorithm 1)."""
    return engine.init_state(key, init_params_fn, engine_spec(pc_cfg, sv_cfg),
                             n_clients)


def build_round_step(loss_fn: Callable, pc_cfg: PrecondConfig,
                     sv_cfg: SavicConfig):
    """loss_fn(params, microbatch) -> scalar.

    Returns ``round_step(state, batch, key)`` where each batch leaf is
    (M, H, ...): H microbatches per client per round. Returns (state, metrics).
    """
    return engine.build_round_step(loss_fn, engine_spec(pc_cfg, sv_cfg))


def _drift(params_m):
    """(1/M)Σ‖x^m − x̂‖² — the V_t of the analysis (0 right after sync)."""
    return engine.client_drift(params_m)


def average_params(state):
    return engine.average_params(state)
