"""VERBATIM pre-compression snapshot of src/repro/core/engine.py (PR 1 state).

Pinning reference for tests/test_compression.py: the post-compression engine
with ``compression.op == "none"`` (or any identity-resolving CompressionSpec)
must emit bit-identical trajectories to this snapshot for every method in
METHODS. Same pattern as _reference_savic.py / _reference_fedopt.py: the
reference runs in-session so the comparison is exact on this backend.

Do not edit (except this header); regenerate by snapshotting engine.py.
One edit since: ``method_spec("savic")`` builds its spec from this module's
own classes, with the values core/savic.py's ``engine_spec`` gave them at
the snapshot, instead of calling the live ``engine_spec``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import preconditioner as PC
from repro.core.preconditioner import PrecondConfig


# --------------------------------------------------------------------------- #
# Specs — one frozen dataclass per layer
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class ClientLoopSpec:
    """H local steps per client: x ← x − lr·D̂⁻¹m,  m ← momentum·m + g."""
    lr: float = 0.1                # local step size (γ of Alg. 1, η_l of [42])
    momentum: float = 0.0          # heavy-ball β₁ on the client
    scaling: str = "global"        # "global" (D̂ updated at sync) | "local"
    # D-stat at sync for global scaling: "avg_grad" (from the client-averaged
    # sync gradient) | "avg_local" (average of per-client stats)
    stat_source: str = "avg_grad"
    weight_decay: float = 0.0
    grad_clip: float = 0.0         # global-norm clip per local step (0 = off)
    use_fused_kernel: bool = False # Pallas scaled_update kernel (TPU)
    reset_momentum: bool = False   # zero m at round start (FedOpt clients)

    def __post_init__(self):
        if self.scaling not in ("global", "local"):
            raise ValueError(self.scaling)


@dataclasses.dataclass(frozen=True)
class SyncSpec:
    """The weighted, optionally quantized, optionally partial sync average."""
    participation: float = 1.0     # fraction of clients entering the average
    sync_dtype: str = ""           # all-reduce dtype ("" = full precision)
    average_momentum: bool = True  # also average momentum buffers at sync


@dataclasses.dataclass(frozen=True)
class ServerSpec:
    """What the server does with the sync average."""
    kind: str = "average"          # "average" (Alg. 1) | "adaptive" ([42])
    opt: str = "adam"              # adagrad | adam | yogi   (adaptive only)
    eta: float = 0.1               # server lr η
    beta1: float = 0.9
    beta2: float = 0.999
    tau: float = 1e-3              # adaptivity floor τ
    v_init: Optional[float] = None # v_{-1}; default τ² (the §5.2 pain point)

    def __post_init__(self):
        if self.kind not in ("average", "adaptive"):
            raise ValueError(self.kind)
        if self.kind == "adaptive" and self.opt not in ("adagrad", "adam",
                                                        "yogi"):
            raise ValueError(self.opt)


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    client: ClientLoopSpec = ClientLoopSpec()
    sync: SyncSpec = SyncSpec()
    server: ServerSpec = ServerSpec()
    precond: PrecondConfig = PrecondConfig(kind="identity")


# --------------------------------------------------------------------------- #
# Method presets — each method is a ~10-line spec
# --------------------------------------------------------------------------- #

METHODS = ("savic", "fedavg", "fedadagrad", "fedadam", "fedyogi", "local-adam")


def method_spec(method: str, *, pc_kind: str = "adam", alpha: float = 1e-2,
                gamma: float = 3e-4, beta1: float = 0.9, scaling: str = "global",
                eta: float = 0.1, eta_l: float = 0.05, tau: float = 1e-3,
                server_beta1: float = 0.9, server_beta2: float = 0.999,
                v_init: Optional[float] = None,
                participation: float = 1.0, sync_dtype: str = "",
                use_fused_kernel: bool = False) -> EngineSpec:
    """Canonical EngineSpec for each named method.

    savic       Algorithm 1: locally-scaled heavy-ball clients, plain average.
    fedavg      plain Local SGD clients (no momentum), plain average.
    fedadagrad / fedadam / fedyogi
                Algorithm 2 of [42]: plain SGD clients (momentum reset each
                round), adaptive server on the pseudo-gradient Δ. ``beta1``
                (client heavy-ball) does not apply; server momentum is
                ``server_beta1``.
    local-adam  composed scenario (cf. 2409.13155): locally-scaled clients
                (per-client D updated every step) AND an adaptive Adam server.
    """
    sync = SyncSpec(participation=participation, sync_dtype=sync_dtype)
    if method == "savic":
        # the SAVIC composition core/savic.py:engine_spec made at this
        # snapshot, in this module's own spec classes (so the reference
        # builds no spec of the code under test)
        return EngineSpec(
            client=ClientLoopSpec(lr=gamma, momentum=beta1, scaling=scaling,
                                  use_fused_kernel=use_fused_kernel),
            sync=sync,
            server=ServerSpec(kind="average"),
            precond=PrecondConfig(kind=pc_kind, alpha=alpha))
    if method == "fedavg":
        # plain Local SGD clients (no momentum), plain average — textbook
        # FedAvg; heavy-ball local SGD is savic with pc_kind="identity"
        return EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=0.0),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="average"),
            precond=PrecondConfig(kind="identity"))
    if method in ("fedadagrad", "fedadam", "fedyogi"):
        return EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=0.0, reset_momentum=True),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="adaptive", opt=method[3:], eta=eta,
                              beta1=server_beta1, beta2=server_beta2, tau=tau,
                              v_init=v_init),
            precond=PrecondConfig(kind="identity"))
    if method == "local-adam":
        return EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=beta1, scaling="local",
                                  use_fused_kernel=use_fused_kernel),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="adaptive", opt="adam", eta=eta,
                              beta1=server_beta1, beta2=server_beta2, tau=tau,
                              v_init=v_init),
            precond=PrecondConfig(kind=pc_kind, alpha=alpha))
    raise ValueError(f"method {method}; expected one of {METHODS}")


# --------------------------------------------------------------------------- #
# State
# --------------------------------------------------------------------------- #


def init_state(key, init_params_fn, spec: EngineSpec, n_clients: int):
    """x_0^m = x_0 (identical start). Server m/v shaped like one replica."""
    params = init_params_fn(key)
    params_m = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_clients,) + p.shape), params)
    mom = jax.tree.map(jnp.zeros_like, params_m)
    if spec.client.scaling == "local":
        pstate = PC.init_state(spec.precond, params_m)  # per-client D (M dim)
        if "d" in pstate:
            pstate["t"] = jnp.zeros((n_clients,), jnp.int32)  # per-client t
    else:
        pstate = PC.init_state(spec.precond, params)    # global D (no M dim)
    state = {
        "params": params_m,
        "mom": mom,
        "precond": pstate,
        "round": jnp.int32(0),
    }
    if spec.server.kind == "adaptive":
        v0 = spec.server.v_init if spec.server.v_init is not None \
            else spec.server.tau ** 2
        state["server"] = {
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(lambda p: jnp.full_like(p, v0), params),
        }
    return state


def average_params(state):
    """The server/averaged point x̂ (clients are identical post-sync)."""
    return jax.tree.map(lambda p: p[0], state["params"])


def client_drift(params_m):
    """(1/M)Σ‖x^m − x̂‖² — the V_t of the analysis (0 right after sync)."""
    def per_leaf(p):
        mean = p.mean(axis=0, keepdims=True)
        return jnp.sum((p - mean) ** 2)
    return sum(jax.tree.leaves(jax.tree.map(per_leaf, params_m)))


# --------------------------------------------------------------------------- #
# ClientLoop
# --------------------------------------------------------------------------- #


def _clip(grads, max_norm):
    if not max_norm:
        return grads
    nrm = jnp.sqrt(sum(jnp.vdot(g, g).real
                       for g in jax.tree.leaves(grads)) + 1e-12)
    scale = jnp.minimum(1.0, max_norm / nrm)
    return jax.tree.map(lambda g: g * scale, grads)


def _apply_update(params, mom, grads, pstate, spec: EngineSpec):
    """x ← x − lr·D̂⁻¹m,  m ← momentum·m + g   (heavy-ball, scaled)."""
    cl, pc = spec.client, spec.precond
    g = grads
    if cl.weight_decay:
        g = jax.tree.map(lambda gi, p: gi + cl.weight_decay * p, g, params)
    mom = jax.tree.map(lambda m, gi: cl.momentum * m + gi, mom, g)
    if cl.use_fused_kernel and pc.kind != "identity":
        from repro.kernels import ops as kops
        params = kops.scaled_update_tree(params, mom, pstate["d"],
                                         cl.lr, pc.alpha,
                                         squared=pc.rule == "squared")
    else:
        direction = PC.precondition(pc, pstate, mom)
        params = jax.tree.map(lambda p, d: p - cl.lr * d, params, direction)
    return params, mom


def _client_loop(loss_fn, grad_fn, spec: EngineSpec):
    """H local steps, vmap-over-M inside a lax.scan over H.

    Returns ``run(params_m, mom_m, pstate, micro, keys) ->
    (params_m, mom_m, pstate, last_grads, losses)`` with micro/keys leading
    (H, M) dims and losses shaped (H, M).
    """
    cl, pc = spec.client, spec.precond

    def local_step_one_client(params, mom, pstate, micro, key):
        """One scaled step on one client. pstate: the client's view of D."""
        loss, grads = grad_fn(params, micro)
        grads = _clip(grads, cl.grad_clip)
        if cl.scaling == "local" and pc.kind != "identity":
            stat = (PC.hutchinson_diag(loss_fn, params, micro, key)
                    if pc.uses_hutchinson else PC.grad_stat(grads))
            if pc.rule == "linear" and not pc.uses_hutchinson:
                stat = jax.tree.map(jnp.abs, grads)
            pstate = PC.update(pc, pstate, stat)
        params, mom = _apply_update(params, mom, grads, pstate, spec)
        return params, mom, pstate, loss, grads

    global_d = cl.scaling == "global"

    def run(params_m, mom_m, pstate, micro, keys):
        def scan_body(carry, xs):
            params_m, mom_m, pstate, _ = carry
            micro_m, ks = xs  # (M, ...) microbatch slice, (M,) keys
            if global_d:
                fn = lambda p, m, mc, k: local_step_one_client(
                    p, m, pstate, mc, k)
                params_m, mom_m, _, losses, grads = jax.vmap(fn)(
                    params_m, mom_m, micro_m, ks)
                new_pstate = pstate
            else:
                fn = local_step_one_client
                params_m, mom_m, new_pstate, losses, grads = jax.vmap(fn)(
                    params_m, mom_m, pstate, micro_m, ks)
            return (params_m, mom_m, new_pstate, grads), losses

        grads0 = jax.tree.map(jnp.zeros_like, params_m)
        (params_m, mom_m, pstate, last_grads), losses = jax.lax.scan(
            scan_body, (params_m, mom_m, pstate, grads0), (micro, keys))
        return params_m, mom_m, pstate, last_grads, losses

    return local_step_one_client, run


# --------------------------------------------------------------------------- #
# SyncStrategy
# --------------------------------------------------------------------------- #


def participation_weights(spec: SyncSpec, key, n_clients: int):
    """Per-client sync weights: uniform 1/M, or 1/n_part on a sampled subset
    (FedAvg-style client sampling); weights always sum to 1."""
    M = n_clients
    n_part = max(1, int(round(spec.participation * M)))
    if n_part < M:
        perm = jax.random.permutation(jax.random.fold_in(key, 3), M)
        return jnp.zeros((M,)).at[perm[:n_part]].set(1.0 / n_part)
    return jnp.full((M,), 1.0 / M)


def make_sync(spec: SyncSpec, key, n_clients: int):
    """The sync average: (M, ...) leaf -> (...) weighted mean.

    With ``sync_dtype`` set, the optimization barriers pin the low-precision
    representation so BOTH legs of the sync (reduce + broadcast-back) move
    sync_dtype bytes; the master-dtype cast happens locally after (quantized
    averaging — same family as the quantization line of related work [19,20];
    sync noise ~2^-8 relative for bf16).
    """
    M = n_clients
    w_part = participation_weights(spec, key, M)

    def _wmean(p):
        wb = w_part.reshape((M,) + (1,) * (p.ndim - 1)).astype(p.dtype)
        return (p * wb).sum(axis=0)

    if spec.sync_dtype:
        sd = jnp.dtype(spec.sync_dtype)

        def avg(p):
            q = jax.lax.optimization_barrier(p.astype(sd))
            a = _wmean(q)
            return jax.lax.optimization_barrier(a)
    else:
        avg = _wmean
    return avg


def _broadcast_back(params_m, avg):
    """Scatter the averaged value back to every client in sync dtype; cast to
    the master dtype locally (cross-device FedAvg semantics: non-participants
    are overwritten too)."""
    return jax.tree.map(
        lambda p, a: jnp.broadcast_to(a[None], (p.shape[0],) + a.shape
                                      ).astype(p.dtype),
        params_m, avg)


# --------------------------------------------------------------------------- #
# ServerUpdate
# --------------------------------------------------------------------------- #


def _adaptive_server_update(spec: ServerSpec, server, x_prev, delta):
    """m/v/x update of Algorithm 2 [42] on the pseudo-gradient Δ."""
    m = jax.tree.map(lambda m_, d: spec.beta1 * m_ + (1 - spec.beta1) * d,
                     server["m"], delta)
    if spec.opt == "adagrad":
        v = jax.tree.map(lambda v_, d: v_ + d * d, server["v"], delta)
    elif spec.opt == "adam":
        v = jax.tree.map(
            lambda v_, d: spec.beta2 * v_ + (1 - spec.beta2) * d * d,
            server["v"], delta)
    else:  # yogi
        v = jax.tree.map(
            lambda v_, d: v_ - (1 - spec.beta2) * d * d
            * jnp.sign(v_ - d * d), server["v"], delta)
    x = jax.tree.map(
        lambda x_, m_, v_: x_ + spec.eta * m_ / (jnp.sqrt(v_) + spec.tau),
        x_prev, m, v)
    return x, {"m": m, "v": v}


# --------------------------------------------------------------------------- #
# The round
# --------------------------------------------------------------------------- #


def build_round_step(loss_fn: Callable, spec: EngineSpec):
    """loss_fn(params, microbatch) -> scalar.

    Returns ``round_step(state, batch, key)`` where each batch leaf is
    (M, H, ...): H microbatches per client per round. Returns (state, metrics).
    Metrics: loss, loss_per_client, client_drift (+ step_norm for adaptive
    servers).
    """
    grad_fn = jax.value_and_grad(loss_fn)
    cl, sy, sv, pc = spec.client, spec.sync, spec.server, spec.precond
    _, client_run = _client_loop(loss_fn, grad_fn, spec)

    def round_step(state, batch, key):
        M = jax.tree.leaves(state["params"])[0].shape[0]
        H = jax.tree.leaves(batch)[0].shape[1]

        # ---- ClientLoop: H local steps, vmap over M inside the scan --------
        keys = jax.random.split(key, (H, M))
        micro = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch)  # (H,M,..)
        mom0 = jax.tree.map(jnp.zeros_like, state["mom"]) \
            if cl.reset_momentum else state["mom"]
        params_m, mom_m, pstate, last_grads, losses = client_run(
            state["params"], mom0, state["precond"], micro, keys)

        drift_pre_sync = client_drift(params_m)

        # ---- SyncStrategy: the only cross-client traffic per round ---------
        avg = make_sync(sy, key, M)
        params_avg = jax.tree.map(avg, params_m)

        if sv.kind == "average":
            params_m = _broadcast_back(params_m, params_avg)
            params_avg = jax.tree.map(lambda x: x[0], params_m)
            if sy.average_momentum:
                mom_m = jax.tree.map(
                    lambda m: jnp.broadcast_to(avg(m)[None],
                                               m.shape).astype(m.dtype), mom_m)

        # ---- D update at sync (global scaling; Algorithm 1 line 4) ---------
        if cl.scaling == "global" and pc.kind != "identity":
            g_last = last_grads  # (M, ...) — grads of the sync step
            if cl.stat_source == "avg_grad":
                g_avg = jax.tree.map(avg, g_last)  # participation+dtype apply
                if pc.uses_hutchinson:
                    sync_micro = jax.tree.map(lambda x: x[-1, 0], micro)
                    stat = PC.hutchinson_diag(loss_fn, params_avg, sync_micro,
                                              jax.random.fold_in(key, 7))
                elif pc.rule == "linear":
                    stat = jax.tree.map(jnp.abs, g_avg)
                else:
                    stat = PC.grad_stat(g_avg)
            else:  # avg_local
                if pc.uses_hutchinson:
                    sync_micro = jax.tree.map(lambda x: x[-1], micro)  # (M,..)
                    hk = jax.random.split(jax.random.fold_in(key, 7), M)
                    stats = jax.vmap(lambda p, mc, k: PC.hutchinson_diag(
                        loss_fn, p, mc, k))(params_m, sync_micro, hk)
                elif pc.rule == "linear":
                    stats = jax.tree.map(jnp.abs, g_last)
                else:
                    stats = PC.grad_stat(g_last)
                stat = jax.tree.map(lambda s: s.mean(axis=0), stats)
            pstate = PC.update(pc, pstate, stat)

        metrics = {
            "loss": losses.mean(),
            "loss_per_client": losses[-1],
            "client_drift": drift_pre_sync,
        }

        # ---- ServerUpdate ---------------------------------------------------
        new_state = {"round": state["round"] + 1, "precond": pstate}
        if sv.kind == "adaptive":
            x_prev = jax.tree.map(lambda p: p[0], state["params"])
            delta = jax.tree.map(
                lambda a, x: a.astype(x.dtype) - x, params_avg, x_prev)
            x_new, server = _adaptive_server_update(sv, state["server"],
                                                    x_prev, delta)
            params_m = _broadcast_back(params_m, x_new)
            new_state["server"] = server
            metrics["step_norm"] = jnp.sqrt(sum(
                jnp.vdot(a - b, a - b).real for a, b in zip(
                    jax.tree.leaves(x_new), jax.tree.leaves(x_prev))))
        new_state["params"] = params_m
        new_state["mom"] = mom_m
        return new_state, metrics

    return round_step
