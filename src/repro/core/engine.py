"""Generic distributed-round engine: ClientLoop × SyncStrategy × ServerUpdate.

The paper describes scaling generically — one analysis, swappable D̂ rules.
This module does the same for the *round structure*: every local method in the
repo (SAVIC / Algorithm 1, the FedOpt baselines of [42], and composed scenarios
such as Local-Adam with an adaptive server, cf. arXiv:2409.13155) is one
configuration of three orthogonal layers:

  * **ClientLoop**   — H local steps on each of M clients, ``vmap`` over M
    inside a ``lax.scan`` over H (XLA provably emits no cross-client collective
    inside the scan). The per-step update is pluggable: plain SGD, heavy-ball,
    or locally-scaled via ``preconditioner.py``.
  * **SyncStrategy** — the only cross-client traffic per round: full mean,
    weighted partial participation (FedAvg-style client sampling), quantized
    ``sync_dtype`` all-reduce, and a pluggable delta **compression** layer
    (``none | topk | randk | int8-stochastic``, optional EF error-feedback
    residual; DESIGN.md §4). Lifted out of SAVIC so *every* method gets them.
  * **ServerUpdate** — what the server does with the synchronized average:
    identity averaging (Algorithm 1), or an adaptive m/v server step
    (FedAdaGrad / FedAdam / FedYogi, Algorithm 2 of [42]).

Distribution contract (see DESIGN.md §2): every client-state leaf carries a
leading client dim M sharded over the plan's client axes; the global D and the
adaptive server's (m, v) are client-replicated (no M dim). The state pytree is

    {"params": (M, ...), "mom": (M, ...), "precond": {...}, "round": i32,
     ["server": {"m": (...), "v": (...)}], ["ef": (M, ...)],
     ["buffer": (B, ...)], ["ctrl": {...}]}

with the ``server`` entry present only for adaptive-server methods, the
``ef`` error-feedback residual (per-client, shaped like ``params``) present
only when the sync compression carries a residual (DESIGN.md §4), and the
``buffer`` staleness FIFO (single-replica shaped, leading B dim) present only
for a staleness-buffered server (``AsyncSpec``, DESIGN.md §5). The ClientLoop
additionally supports a per-client local-step vector H_m
(``ClientLoopSpec.local_steps``), realized as masking inside the same
scan×vmap program.

``core/savic.py`` and ``core/fedopt.py`` are thin method definitions over this
engine; new methods are a ~50-line preset (see ``method_spec``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import controller as CTRL
from repro.core.controller import ControllerSpec
from repro.core import preconditioner as PC
from repro.core.preconditioner import PrecondConfig


# --------------------------------------------------------------------------- #
# Specs — one frozen dataclass per layer
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class ClientLoopSpec:
    """H local steps per client: x ← x − lr·D̂⁻¹m,  m ← momentum·m + g.

    ``local_steps`` is the per-client local-step vector H_m (systems
    heterogeneity, DESIGN.md §5): client m performs ``local_steps[m]`` of the
    round's H microbatch steps and then idles at the sync barrier. Implemented
    as masking inside the scan-over-H × vmap-over-M program — one jit'd
    computation regardless of how ragged H_m is. ``None`` (or all entries
    equal to the batch's H) is the uniform regime and emits the exact
    pre-heterogeneity program.
    """
    lr: float = 0.1                # local step size (γ of Alg. 1, η_l of [42])
    momentum: float = 0.0          # heavy-ball β₁ on the client
    scaling: str = "global"        # "global" (D̂ updated at sync) | "local"
    # D-stat at sync for global scaling: "avg_grad" (from the client-averaged
    # sync gradient) | "avg_local" (average of per-client stats)
    stat_source: str = "avg_grad"
    weight_decay: float = 0.0
    grad_clip: float = 0.0         # global-norm clip per local step (0 = off)
    reset_momentum: bool = False   # zero m at round start (FedOpt clients)
    local_steps: Optional[tuple] = None  # per-client H_m (None = uniform H)

    def __post_init__(self):
        if self.scaling not in ("global", "local"):
            raise ValueError(self.scaling)
        if self.local_steps is not None:
            hs = tuple(int(h) for h in self.local_steps)
            if not hs or any(h < 1 for h in hs):
                raise ValueError(f"local_steps must be a non-empty tuple of "
                                 f"ints >= 1, got {self.local_steps!r}")
            object.__setattr__(self, "local_steps", hs)


COMPRESSION_OPS = ("none", "topk", "randk", "int8-stochastic")


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Compression of the client→server round delta Δ_m = x_{m,H} − x_t.

    Operators (DESIGN.md §4; cf. arXiv:2109.05109 / arXiv:2409.13155):
      none             identity — the uncompressed sync path, bit-for-bit.
      topk             keep the k·dim largest-|Δ| entries per leaf per client
                       (biased — pair with ``error_feedback``).
      randk            keep k·dim uniformly sampled entries, rescaled by
                       dim/(k·dim) so the compressor is unbiased. With
                       ``error_feedback`` the rescale is dropped: EF needs a
                       contractive compressor, and the dim/k amplification
                       would grow the residual ~(dim/k − 1)× per round
                       (unrescaled randk is a masking sparsifier, so the EF
                       residual is its exact complement, like topk).
      int8-stochastic  per-(client, leaf) absmax/127 scale, stochastic-round
                       int8 encode + fp32 decode (unbiased). With
                       ``use_fused_kernel`` the encode+decode runs as the
                       fused Pallas ``quantize_update`` kernel.

    ``error_feedback`` carries the EF residual e_m in the state pytree
    (``state["ef"]``, leading M dim): u_m = Δ_m + e_m is compressed instead of
    Δ_m and e'_m = u_m − C(u_m) is what the wire dropped this round.
    """
    op: str = "none"
    k: float = 1.0                 # kept fraction per leaf (topk / randk)
    error_feedback: bool = False   # EF residual buffer (state["ef"])
    use_fused_kernel: bool = False # Pallas quantize_update (int8-stochastic)

    def __post_init__(self):
        if self.op not in COMPRESSION_OPS:
            raise ValueError(
                f"compression op {self.op!r}; expected one of {COMPRESSION_OPS}")
        if not 0.0 < self.k <= 1.0:
            raise ValueError(f"compression k={self.k}; expected 0 < k <= 1")

    def is_identity(self) -> bool:
        """True iff this spec provably compresses nothing. The engine then
        emits the exact uncompressed sync program (the bit-for-bit contract
        pinned by tests/test_compression.py) and carries no ``ef`` leaf."""
        return self.op == "none" or (self.op in ("topk", "randk")
                                     and self.k >= 1.0)


STALENESS_WEIGHTINGS = ("constant", "polynomial")


@dataclasses.dataclass(frozen=True)
class AsyncSpec:
    """FedBuff-style server staleness buffer (DESIGN.md §5).

    With ``buffer_rounds = B > 0`` the server keeps a delta FIFO
    ``state["buffer"]`` of the last B participation-weighted round deltas
    Δ̄(t), Δ̄(t−1), …, Δ̄(t−B+1) (single-replica shaped, leading B dim, sharded
    like one replica's params). Each round the freshly aggregated delta is
    enqueued and the server applies the staleness-weighted combination

        Δ_applied(t) = Σ_τ w_τ · Δ̄(t−τ),   w_τ ∝ s(τ)·[t ≥ τ],  Σ_τ w_τ = 1

    with s(τ) = 1 (``constant``) or (1+τ)^-poly_a (``polynomial``,
    cf. FedBuff / arXiv:2106.06639's staleness scaling). Because every delta
    transits each slot exactly once, its total applied mass is 1 — the buffer
    is a staleness-weighted smoothing of the update stream, which is what a
    lag-τ asynchronous server pace simulates in a single-program round loop.

    ``buffer_rounds = 0`` is fully synchronous and emits the exact
    pre-buffer program (identity short-circuit, same discipline as
    ``CompressionSpec.is_identity``). B = 1 holds only fresh deltas
    (staleness 0) and reduces to plain delta averaging.
    """
    buffer_rounds: int = 0         # B; 0 = fully synchronous (identity)
    weighting: str = "constant"    # staleness weight s(τ)
    poly_a: float = 0.5            # exponent for the polynomial weighting

    def __post_init__(self):
        if int(self.buffer_rounds) != self.buffer_rounds \
                or self.buffer_rounds < 0:
            raise ValueError(f"buffer_rounds={self.buffer_rounds}; expected "
                             f"an int >= 0")
        object.__setattr__(self, "buffer_rounds", int(self.buffer_rounds))
        if self.weighting not in STALENESS_WEIGHTINGS:
            raise ValueError(f"staleness weighting {self.weighting!r}; "
                             f"expected one of {STALENESS_WEIGHTINGS}")
        if self.poly_a <= 0.0:
            raise ValueError(f"poly_a={self.poly_a}; expected > 0")

    def is_identity(self) -> bool:
        """True iff no buffering happens: the engine emits the bit-exact
        synchronous program and carries no ``buffer`` leaf."""
        return self.buffer_rounds == 0


@dataclasses.dataclass(frozen=True)
class SyncSpec:
    """The weighted, optionally quantized/compressed, optionally partial,
    optionally staleness-buffered sync average.

    ``personal`` (DESIGN.md §12) is a tuple of path substrings naming
    CLIENT-RESIDENT parameter leaves — a personalization mask. A leaf whose
    "/"-joined tree path contains any pattern (e.g. ``("final_norm",)`` for
    the LM's local head) is excluded from the entire sync surface: it is
    never averaged, compressed, buffered, EF-tracked, broadcast back, or fed
    to the adaptive server — each client keeps its own copy across rounds,
    exactly like the per-client D under local scaling. The empty default
    touches nothing: the engine emits the bit-exact pre-personalization
    program.
    """
    participation: float = 1.0     # fraction of clients entering the average
    sync_dtype: str = ""           # all-reduce dtype ("" = full precision)
    average_momentum: bool = True  # also average momentum buffers at sync
    compression: CompressionSpec = CompressionSpec()
    asynchrony: AsyncSpec = AsyncSpec()
    personal: tuple = ()           # client-resident leaf path patterns

    def __post_init__(self):
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(f"participation={self.participation}; "
                             f"expected 0 < p <= 1")
        if isinstance(self.personal, str):
            # a bare string would silently become a tuple of characters
            raise ValueError(f"personal={self.personal!r}; expected a tuple "
                             f"of path-substring patterns, not a bare string")
        pats = tuple(self.personal) if self.personal else ()
        if not all(isinstance(p, str) and p for p in pats):
            raise ValueError(f"personal={self.personal!r}; expected a tuple "
                             f"of non-empty path-substring patterns")
        object.__setattr__(self, "personal", pats)
        if self.sync_dtype:
            try:
                jnp.dtype(self.sync_dtype)
            except TypeError:
                raise ValueError(f"sync_dtype {self.sync_dtype!r} is not a "
                                 f"dtype") from None
        if not isinstance(self.compression, CompressionSpec):
            raise ValueError(f"compression must be a CompressionSpec, got "
                             f"{type(self.compression).__name__}")
        if not isinstance(self.asynchrony, AsyncSpec):
            raise ValueError(f"asynchrony must be an AsyncSpec, got "
                             f"{type(self.asynchrony).__name__}")


@dataclasses.dataclass(frozen=True)
class ServerSpec:
    """What the server does with the sync average.

    ``sync_dtype`` / ``sync_k`` compress the **server** adaptive state m/v
    (arXiv:2109.05109 regime): replicas agreeing on the adaptive server step
    only need the compressed view, so the per-round server-state sync leg
    stops scaling with the full fp32 m/v trees. ``sync_k < 1`` keeps one
    shared largest-|m| index set per leaf for both trees (a dropped
    coordinate contributes no step; its v falls back to the v_init floor);
    ``sync_dtype`` round-trips both trees through that dtype (QDQ behind
    optimization barriers, same discipline as ``SyncSpec.sync_dtype``).
    Defaults are the identity: bit-exact pre-feature program.
    """
    kind: str = "average"          # "average" (Alg. 1) | "adaptive" ([42])
    opt: str = "adam"              # adagrad | adam | yogi   (adaptive only)
    eta: float = 0.1               # server lr η
    beta1: float = 0.9
    beta2: float = 0.999
    tau: float = 1e-3              # adaptivity floor τ
    v_init: Optional[float] = None # v_{-1}; default τ² (the §5.2 pain point)
    sync_dtype: str = ""           # m/v sync dtype ("" = full precision)
    sync_k: float = 1.0            # kept fraction of the m/v trees (top-|m|)

    def __post_init__(self):
        if self.kind not in ("average", "adaptive"):
            raise ValueError(self.kind)
        if self.kind == "adaptive" and self.opt not in ("adagrad", "adam",
                                                        "yogi"):
            raise ValueError(self.opt)
        if not 0.0 < self.sync_k <= 1.0:
            raise ValueError(f"sync_k={self.sync_k}; expected 0 < k <= 1")
        if self.sync_dtype:
            try:
                jnp.dtype(self.sync_dtype)
            except TypeError:
                raise ValueError(f"sync_dtype {self.sync_dtype!r} is not a "
                                 f"dtype") from None
        if self.kind == "average" and not self.sync_identity():
            raise ValueError("server sync_dtype/sync_k compress the adaptive "
                             "m/v state; an averaging server has none")

    def sync_identity(self) -> bool:
        """True iff the server m/v state moves uncompressed (bit-exact)."""
        return not self.sync_dtype and self.sync_k >= 1.0


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    client: ClientLoopSpec = ClientLoopSpec()
    sync: SyncSpec = SyncSpec()
    server: ServerSpec = ServerSpec()
    precond: PrecondConfig = PrecondConfig(kind="identity")
    # adaptive communication-budget controller (core/controller.py,
    # DESIGN.md §10); the disabled default adds no state leaf and changes
    # no program
    controller: ControllerSpec = ControllerSpec()

    def __post_init__(self):
        if not isinstance(self.controller, ControllerSpec):
            raise ValueError(f"controller must be a ControllerSpec, got "
                             f"{type(self.controller).__name__}")


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
                compression="none", compression_k: float = 1.0,
                error_feedback: bool = False,
                local_steps: Optional[tuple] = None,
                asynchrony=None, async_buffer: int = 0,
                staleness_weight: str = "constant",
                server_sync_dtype: str = "", server_sync_k: float = 1.0,
                controller: Optional[ControllerSpec] = None,
                personal: tuple = ()) -> EngineSpec:
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

    ``compression`` is either a CompressionSpec or an operator name (then
    ``compression_k`` / ``error_feedback`` fill in the rest) — every method
    gets compressed sync for free, opening the compressed-FedAdam /
    compressed-Local-Adam scenario family.

    ``local_steps`` (per-client H_m) and ``asynchrony`` (an AsyncSpec; or the
    ``async_buffer``/``staleness_weight`` shorthand) are engine-level too:
    every method runs under systems heterogeneity and a staleness-buffered
    server (DESIGN.md §5). ``controller`` (a ControllerSpec) and the
    ``server_sync_dtype``/``server_sync_k`` server-state compression are
    likewise method-agnostic (DESIGN.md §10). ``personal`` is the
    client-resident leaf mask (``SyncSpec.personal``, DESIGN.md §12) —
    method-agnostic too, though methods with a GLOBAL non-identity D (savic's
    default scaling) must switch to ``scaling="local"`` to combine with it.
    """
    comp = compression if isinstance(compression, CompressionSpec) \
        else CompressionSpec(op=compression, k=compression_k,
                             error_feedback=error_feedback)
    asy = asynchrony if isinstance(asynchrony, AsyncSpec) \
        else AsyncSpec(buffer_rounds=async_buffer, weighting=staleness_weight)
    sync = SyncSpec(participation=participation, sync_dtype=sync_dtype,
                    compression=comp, asynchrony=asy)
    if method == "savic":
        # one source of truth for the SAVIC composition: SavicConfig ->
        # engine_spec in core/savic.py (lazy import; savic imports engine)
        from repro.core.savic import SavicConfig, engine_spec
        spec = engine_spec(
            PrecondConfig(kind=pc_kind, alpha=alpha),
            SavicConfig(gamma=gamma, beta1=beta1, scaling=scaling,
                        participation=participation, sync_dtype=sync_dtype,
                        compression=comp, local_steps=local_steps,
                        asynchrony=asy))
    elif method == "fedavg":
        # plain Local SGD clients (no momentum), plain average — textbook
        # FedAvg; heavy-ball local SGD is savic with pc_kind="identity"
        spec = EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=0.0,
                                  local_steps=local_steps),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="average"),
            precond=PrecondConfig(kind="identity"))
    elif method in ("fedadagrad", "fedadam", "fedyogi"):
        spec = EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=0.0, reset_momentum=True,
                                  local_steps=local_steps),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="adaptive", opt=method[3:], eta=eta,
                              beta1=server_beta1, beta2=server_beta2, tau=tau,
                              v_init=v_init, sync_dtype=server_sync_dtype,
                              sync_k=server_sync_k),
            precond=PrecondConfig(kind="identity"))
    elif method == "local-adam":
        spec = EngineSpec(
            client=ClientLoopSpec(lr=eta_l, momentum=beta1, scaling="local",
                                  local_steps=local_steps),
            sync=dataclasses.replace(sync, average_momentum=False),
            server=ServerSpec(kind="adaptive", opt="adam", eta=eta,
                              beta1=server_beta1, beta2=server_beta2, tau=tau,
                              v_init=v_init, sync_dtype=server_sync_dtype,
                              sync_k=server_sync_k),
            precond=PrecondConfig(kind=pc_kind, alpha=alpha))
    else:
        raise ValueError(f"method {method}; expected one of {METHODS}")
    if spec.server.kind == "average" and (server_sync_dtype
                                          or server_sync_k < 1.0):
        raise ValueError(f"{method} has an averaging server: no adaptive "
                         f"m/v state to compress")
    if controller is not None:
        spec = dataclasses.replace(spec, controller=controller)
    if personal:
        spec = dataclasses.replace(
            spec, sync=dataclasses.replace(spec.sync,
                                           personal=tuple(personal)))
    return spec


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
    # personalization (DESIGN.md §12): server/ef/buffer state exists only for
    # the SYNCED leaves — personal leaves never reach the sync surface, so
    # their slots are None-stripped out of every server-side tree. The empty
    # mask strips nothing: bit-exact pre-personalization state.
    personal = spec.sync.personal
    params_sync = strip_personal(personal, params)
    if spec.server.kind == "adaptive":
        v0 = spec.server.v_init if spec.server.v_init is not None \
            else spec.server.tau ** 2
        state["server"] = {
            "m": jax.tree.map(jnp.zeros_like, params_sync),
            "v": jax.tree.map(lambda p: jnp.full_like(p, v0), params_sync),
        }
    comp = spec.sync.compression
    if comp.error_feedback and not comp.is_identity():
        # EF residual e_m: per-client, shaped like params (DESIGN.md §4).
        # Identity compression drops nothing, so the leaf would stay zero —
        # omitted to keep the state pytree (and program) bit-identical.
        state["ef"] = jax.tree.map(jnp.zeros_like,
                                   strip_personal(personal, params_m))
    asy = spec.sync.asynchrony
    if not asy.is_identity():
        # staleness delta FIFO: single-replica shaped, leading B dim, sharded
        # like one replica's params (DESIGN.md §5) — server state, like m/v
        state["buffer"] = jax.tree.map(
            lambda p: jnp.zeros((asy.buffer_rounds,) + p.shape, p.dtype),
            params_sync)
    if spec.controller.enabled:
        # controller knobs + EMA stats (DESIGN.md §10): small scalar/(M,)
        # leaves that ride the state pytree through checkpoint/shard/donate
        state["ctrl"] = CTRL.init_ctrl_state(spec.controller, n_clients)
    return state


def strip_personal(personal: tuple, tree, is_leaf=None):
    """Replace every personal leaf (path contains a ``personal`` pattern)
    with ``None`` — jax pytrees treat ``None`` as an empty subtree, so the
    stripped tree's leaves are exactly the SYNCED leaves: ``jax.tree.map``
    over stripped trees touches no personal state and ``jax.tree.leaves``
    counts no personal bytes. The empty mask returns the tree unchanged
    (bit-exact identity; DESIGN.md §12)."""
    if not personal:
        return tree
    # ``is_leaf`` lets the launch layer strip trees whose leaves are
    # themselves containers (PartitionSpec tuples in sharding-spec trees)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=is_leaf)
    new = [None if _is_personal(personal, path) else leaf
           for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, new)


def _path_str(path) -> str:
    """'/'-joined keys of a pytree path (the form ``personal`` matches)."""
    keys = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            keys.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    return "/".join(keys)


def _is_personal(personal: tuple, path) -> bool:
    s = _path_str(path)
    return any(pat in s for pat in personal)


def _merge_personal(stripped, full, merge_fn):
    """Recombine a synced (None-stripped) tree with the full per-client tree:
    personal positions keep ``full``'s leaf, synced positions get
    ``merge_fn(stripped_leaf, full_leaf)``. Treating ``None`` as a leaf makes
    the stripped tree's structure match the full one's."""
    return jax.tree.map(
        lambda s, f: f if s is None else merge_fn(s, f),
        stripped, full, is_leaf=lambda x: x is None)


def average_params(state):
    """The server/averaged point x̂ (clients are identical post-sync)."""
    return jax.tree.map(lambda p: p[0], state["params"])


def _leaf_drift(p):
    # the clients' mean and the sum over them cross clients as the sync's
    # average does: on a mesh, one client a chip, all-reduces of the leaf
    with jax.named_scope("exchange"):
        mean = p.mean(axis=0, keepdims=True)
        return jnp.sum((p - mean) ** 2)


def client_drift(params_m):
    """(1/M)Σ‖x^m − x̂‖² — the V_t of the analysis (0 right after sync)."""
    return sum(jax.tree.leaves(jax.tree.map(_leaf_drift, params_m)))


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
    direction = PC.precondition(pc, pstate, mom)
    params = jax.tree.map(lambda p, d: p - cl.lr * d, params, direction)
    return params, mom


def _objective_grad(objective):
    """Keyed value-and-grad of a non-identity ClientObjective: the per-step
    key is folded by ``_OBJECTIVE_FOLD`` so the objective's noise stream
    (consistency views, token dropout) is decoupled from the Hutchinson
    probe and every other consumer of the step key (DESIGN.md §12)."""
    from repro.core.objectives import _OBJECTIVE_FOLD
    vg = jax.value_and_grad(objective.loss)

    def grad3(params, micro, key):
        return vg(params, micro, jax.random.fold_in(key, _OBJECTIVE_FOLD))
    return grad3


def _client_loop(loss_fn, grad_fn, spec: EngineSpec, objective=None):
    """H local steps, vmap-over-M inside a lax.scan over H.

    Returns ``run(params_m, mom_m, pstate, micro, keys, h_m=None) ->
    (params_m, mom_m, pstate, last_grads, losses)`` with micro/keys leading
    (H, M) dims and losses shaped (H, M). ``h_m`` is an optional TRACED (M,)
    int32 per-client step budget (the controller's round-addressable H_m,
    DESIGN.md §10): same masking machinery as the static ``local_steps``
    vector but with the bound read from state — no recompile as it moves.

    ``objective`` (an optional ``objectives.ClientObjective``) swaps the
    differentiated loss: a non-identity objective is consulted with the
    per-step key (semi-supervised losses are stochastic); ``None`` or an
    identity objective leaves the unkeyed ``grad_fn`` call — and hence the
    emitted program — bit-exactly as before (DESIGN.md §12). The D̂
    curvature probes keep using the supervised ``loss_fn``: Assumption-4
    scaling tracks the geometry of the task loss, not the regularizer.
    """
    cl, pc = spec.client, spec.precond
    semi = objective is not None and not objective.is_identity()
    obj_grad = _objective_grad(objective) if semi else None

    def local_step_one_client(params, mom, pstate, micro, key):
        """One scaled step on one client. pstate: the client's view of D."""
        with jax.named_scope("model"):
            if semi:
                loss, grads = obj_grad(params, micro, key)
            else:
                loss, grads = grad_fn(params, micro)
        with jax.named_scope("local_step"):
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

    def run(params_m, mom_m, pstate, micro, keys, h_m=None):
        H = jax.tree.leaves(micro)[0].shape[0]
        M = jax.tree.leaves(params_m)[0].shape[0]
        masked = _needs_masking(cl, H, M) or h_m is not None
        bound = h_m if h_m is not None \
            else (jnp.asarray(cl.local_steps, jnp.int32)
                  if cl.local_steps is not None else None)

        def scan_body(carry, xs):
            params_m, mom_m, pstate, grads_c = carry
            if masked:
                micro_m, ks, h_idx = xs
                active = h_idx < bound  # (M,)
            else:
                micro_m, ks = xs  # (M, ...) microbatch slice, (M,) keys
            if global_d:
                fn = lambda p, m, mc, k: local_step_one_client(
                    p, m, pstate, mc, k)
                new_params, new_mom, _, losses, grads = jax.vmap(fn)(
                    params_m, mom_m, micro_m, ks)
                new_pstate = pstate
            else:
                fn = local_step_one_client
                new_params, new_mom, new_pstate, losses, grads = jax.vmap(fn)(
                    params_m, mom_m, pstate, micro_m, ks)
            if masked:
                # heterogeneous H_m: clients past their budget freeze —
                # params/mom/grads (and per-client D) keep their step-H_m
                # values, so x_{m,H} = x_{m,H_m} at the sync barrier
                sel = lambda n, o: jax.tree.map(
                    lambda a, b: jnp.where(
                        active.reshape((M,) + (1,) * (a.ndim - 1)), a, b),
                    n, o)
                with jax.named_scope("local_step"):
                    new_params = sel(new_params, params_m)
                    new_mom = sel(new_mom, mom_m)
                    grads = sel(grads, grads_c)
                    if not global_d:
                        new_pstate = sel(new_pstate, pstate)
            return (new_params, new_mom, new_pstate, grads), losses

        grads0 = jax.tree.map(jnp.zeros_like, params_m)
        xs = (micro, keys, jnp.arange(H, dtype=jnp.int32)) if masked \
            else (micro, keys)
        (params_m, mom_m, pstate, last_grads), losses = jax.lax.scan(
            scan_body, (params_m, mom_m, pstate, grads0), xs)
        return params_m, mom_m, pstate, last_grads, losses

    return run


def _needs_masking(cl: ClientLoopSpec, H: int, M: int) -> bool:
    """True iff the per-client H_m vector actually truncates some client.

    Uniform H_m == H (or ``local_steps=None``) short-circuits to the exact
    pre-heterogeneity program — the bit-for-bit contract of DESIGN.md §5,
    pinned by tests/test_heterogeneity.py. Shape errors are raised at trace
    time, where H and M are static.
    """
    hs = cl.local_steps
    if hs is None:
        return False
    if len(hs) != M:
        raise ValueError(f"local_steps has {len(hs)} entries for {M} clients")
    if max(hs) > H:
        raise ValueError(f"local_steps max {max(hs)} exceeds the round's "
                         f"H={H} microbatches")
    return any(h != H for h in hs)


# --------------------------------------------------------------------------- #
# Compression (DESIGN.md §4)
# --------------------------------------------------------------------------- #


def _k_count(k: float, n: int) -> int:
    """Static kept-entry count for a leaf of n elements (at least 1).

    Half-up rounding: Python ``round`` banker's-rounds halves to even
    (round(2.5) == 2), which made k = 0.5 on an odd-n leaf keep ⌊k·n⌋.
    """
    return max(1, min(n, int(math.floor(k * n + 0.5))))


def _compress_leaf(spec: CompressionSpec, x, key, k_frac=None):
    """Apply one compression operator to a (M, ...) leaf of round deltas.

    Per-client semantics throughout: topk/randk select EXACTLY k·n entries
    per client row, int8-stochastic uses a per-client absmax/127 scale.
    Returns the decoded (server-side) fp32 view of what crossed the wire,
    same shape as x.

    ``k_frac`` (optional traced f32 scalar) overrides ``spec.k`` for
    topk/randk with the controller's round-addressable kept fraction
    (DESIGN.md §10): selection goes through stable ranks so the count is a
    traced value and the program never recompiles as k moves. Both paths
    break score ties toward the lower index, so a frozen ``k_frac`` equal to
    a binary-exact ``spec.k`` selects the identical entry set bitwise.
    """
    M = x.shape[0]
    flat = x.reshape(M, -1)
    n = flat.shape[1]
    if spec.op in ("topk", "randk"):
        # randk = topk on uniform scores: same selection code, random ranking
        scores = jnp.abs(flat) if spec.op == "topk" \
            else jax.random.uniform(key, flat.shape)
        if k_frac is None:
            # exact-k: scatter the top_k index set. (Thresholding with
            # `scores >= thresh` kept EVERY tied entry — k=0.5 on a
            # 4-element all-equal row kept 4/4 — corrupting the wire
            # accounting and randk's n/kc unbiased rescale.)
            kc = _k_count(spec.k, n)
            idx = jax.lax.top_k(scores, kc)[1]
            mask = jnp.zeros(flat.shape, jnp.bool_).at[
                jnp.arange(M)[:, None], idx].set(True)
            inv = n / kc
        else:
            # traced count: entry kept iff its stable descending rank < kc
            kc = jnp.clip(jnp.floor(k_frac * n + 0.5).astype(jnp.int32), 1, n)
            order = jnp.argsort(-scores, axis=1)      # stable: ties low-first
            ranks = jnp.argsort(order, axis=1)
            mask = ranks < kc
            inv = n / kc.astype(flat.dtype)
        kept = jnp.where(mask, flat, 0.0)
        if spec.op == "randk" and not spec.error_feedback:
            # unbiased rescale E[C(x)] = x — only without EF: the dim/k
            # amplification is non-contractive and blows up the residual
            kept = kept * inv
        return kept.reshape(x.shape)
    # int8-stochastic: E[floor(v + U[0,1))] = v — unbiased QDQ
    absmax = jnp.max(jnp.abs(flat), axis=1, keepdims=True)
    scale = absmax / 127.0
    u01 = jax.random.uniform(key, flat.shape)
    if spec.use_fused_kernel:
        from repro.kernels import ops as kops
        _, dec = kops.quantize_update(flat, u01, scale)
    else:
        # one source of truth for the QDQ formula: the kernel's jnp oracle
        # (the Pallas kernel is pinned bit-identical to it)
        from repro.kernels import ref as kref
        _, dec = kref.quantize_update_ref(flat, u01, scale)
    return dec.reshape(x.shape)


def compress_tree(spec: CompressionSpec, deltas, key, k_frac=None):
    """Compress a pytree of (M, ...) round deltas; per-leaf folded keys."""
    leaves, treedef = jax.tree.flatten(deltas)
    keys = jax.random.split(jax.random.fold_in(key, 17), len(leaves))
    return jax.tree.unflatten(
        treedef,
        [_compress_leaf(spec, x, k, k_frac) for x, k in zip(leaves, keys)])


def measured_wire_bytes(comp: CompressionSpec, compressed,
                        elem_bytes: int = 4):
    """Encoded client→server payload measured from the ACTUAL arrays
    ``compress_tree`` emitted (its decoded (M, ...) views) — the ground truth
    ``bytes_on_wire``'s analytic accounting is pinned against
    (tests/test_compression.py).

    Per client: topk/randk count the surviving nonzero entries, each a
    (fp32 value, int32 index) pair; int8-stochastic moves 1 byte/element plus
    one fp32 scale per leaf; identity specs move every element at
    ``elem_bytes``. Returns an int64 numpy array of shape (M,). Caveat: a
    kept-but-exactly-zero delta entry is indistinguishable from a dropped one
    in the decoded view, so topk/randk counts are exact only for continuous
    deltas (which is what the engine compresses).
    """
    import numpy as np
    leaves = jax.tree.leaves(compressed)
    M = leaves[0].shape[0]
    total = np.zeros((M,), np.int64)
    for leaf in leaves:
        flat = np.asarray(leaf).reshape(M, -1)
        n = flat.shape[1]
        if comp.is_identity():
            total += n * elem_bytes
        elif comp.op in ("topk", "randk"):
            total += (flat != 0).sum(axis=1).astype(np.int64) * (4 + 4)
        else:  # int8-stochastic
            total += n * 1 + 4
    return total


def bytes_on_wire(spec: EngineSpec, params) -> dict:
    """Analytic client→server sync payload per round for ONE client.

    ``params`` is a single-replica pytree (arrays or ShapeDtypeStructs, no
    leading M dim). Accounting: topk/randk send (fp32 value, int32 index)
    pairs; int8-stochastic sends 1 byte/element + one fp32 scale per leaf;
    uncompressed legs move ``sync_dtype`` bytes (fp32 when unset). Momentum,
    when averaged (``average_momentum`` under an averaging server), always
    moves uncompressed.

    Personal (client-resident) leaves move NOTHING: they are stripped from
    every leg — delta, momentum, and the server m/v sync — before counting,
    so the reported payload is exactly the synced subset's (the synced
    leaves' accounting is unchanged by personalization; DESIGN.md §12).
    """
    params = strip_personal(spec.sync.personal, params)
    sy, comp = spec.sync, spec.sync.compression
    elem = jnp.dtype(sy.sync_dtype).itemsize if sy.sync_dtype else 4
    delta = raw = 0
    for leaf in jax.tree.leaves(params):
        n = 1
        for s in leaf.shape:
            n *= int(s)
        raw += n * 4
        if comp.is_identity():
            delta += n * elem
        elif comp.op in ("topk", "randk"):
            delta += _k_count(comp.k, n) * (4 + 4)
        else:  # int8-stochastic
            delta += n * 1 + 4
    mom = raw if (spec.server.kind == "average"
                  and sy.average_momentum) else 0
    if mom and sy.sync_dtype:
        mom = mom // 4 * elem
    out = {"delta_bytes": delta, "momentum_bytes": mom,
           "total_bytes": delta + mom, "uncompressed_bytes": raw + mom,
           "compression_x": round((raw + mom) / max(delta + mom, 1), 2)}
    if spec.server.kind == "adaptive":
        # the server m/v sync leg (replica agreement on the adaptive state,
        # arXiv:2109.05109) — a server→server cost, reported separately and
        # NOT folded into the client→server total_bytes above
        sv = spec.server
        elem_s = jnp.dtype(sv.sync_dtype).itemsize if sv.sync_dtype else 4
        s_raw = s_comp = 0
        for leaf in jax.tree.leaves(params):
            n = 1
            for s in leaf.shape:
                n *= int(s)
            s_raw += 2 * n * 4                  # fp32 m + v
            if sv.sync_k < 1.0:
                # shared top-|m| index set: (m, v) value pair + one index
                s_comp += _k_count(sv.sync_k, n) * (2 * elem_s + 4)
            else:
                s_comp += 2 * n * elem_s
        out["server_state_bytes"] = s_comp
        out["server_state_uncompressed_bytes"] = s_raw
    return out


# --------------------------------------------------------------------------- #
# SyncStrategy
# --------------------------------------------------------------------------- #


def staleness_weights(spec: AsyncSpec, round_idx, b_eff=None):
    """Normalized weights over the delta FIFO's B slots (ages τ = 0..B−1).

    w_τ ∝ s(τ)·[round_idx ≥ τ]: slot τ holds the delta aggregated τ rounds
    ago, which does not exist before round τ (the buffer starts zeroed), so
    early rounds renormalize over the populated prefix. Weights always sum to
    1 (pinned in tests/test_heterogeneity.py); with B = 1 the single fresh
    slot gets weight 1 — plain delta averaging.

    ``b_eff`` (optional traced i32 scalar in [1, B]) is the controller's
    effective staleness depth (DESIGN.md §10): ages >= b_eff are masked to 0,
    shrinking the applied window inside the statically allocated FIFO with no
    recompile. ``None`` is the bit-exact static program.
    """
    B = spec.buffer_rounds
    ages = jnp.arange(B, dtype=jnp.float32)
    s = jnp.ones((B,)) if spec.weighting == "constant" \
        else (1.0 + ages) ** (-spec.poly_a)
    w = s * (ages <= round_idx)
    if b_eff is not None:
        w = w * (ages < b_eff)
    return w / jnp.maximum(w.sum(), jnp.finfo(jnp.float32).tiny)


def participation_weights(spec: SyncSpec, key, n_clients: int):
    """Per-client sync weights: uniform 1/M, or 1/n_part on a sampled subset
    (FedAvg-style client sampling); weights always sum to 1. Half-up count:
    Python round() banker's-rounds (participation=0.5, M=5 sampled 2)."""
    M = n_clients
    n_part = max(1, int(math.floor(spec.participation * M + 0.5)))
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

    The average runs under the named scope ``exchange``, as the client
    drift's mean does (``_leaf_drift``): where client state crosses
    clients, so on a mesh, one client a chip, the partitioner's all-reduces
    carry that scope.
    """
    M = n_clients
    w_part = participation_weights(spec, key, M)

    def _wmean(p):
        wb = w_part.reshape((M,) + (1,) * (p.ndim - 1)).astype(p.dtype)
        return (p * wb).sum(axis=0)

    if spec.sync_dtype:
        sd = jnp.dtype(spec.sync_dtype)

        def mean(p):
            q = jax.lax.optimization_barrier(p.astype(sd))
            a = _wmean(q)
            return jax.lax.optimization_barrier(a)
    else:
        mean = _wmean

    def avg(p):
        with jax.named_scope("exchange"):
            return mean(p)
    return avg


def _broadcast_back(params_m, avg):
    """Scatter the averaged value back to every client in sync dtype; cast to
    the master dtype locally (cross-device FedAvg semantics: non-participants
    are overwritten too). ``avg`` may be a None-stripped synced tree
    (personalization): personal positions keep each client's own leaf."""
    return _merge_personal(
        avg, params_m,
        lambda a, p: jnp.broadcast_to(a[None], (p.shape[0],) + a.shape
                                      ).astype(p.dtype))


def _one_pass(spec: EngineSpec, mesh=None) -> bool:
    """True iff the round's sync is the plain weighted average broadcast back
    to every client — an averaging server, no ``sync_dtype``, compression
    or staleness buffer — on one device (no ``mesh``, or a mesh of one):
    then each client leaf that tiles is synced by the one-pass kernel
    (``kernels/sync_average.py``). Under GSPMD the kernel would gather the
    client-sharded state, so a round step built for a mesh of several
    devices keeps the jnp sync."""
    sy = spec.sync
    return ((mesh is None or mesh.size == 1)
            and spec.server.kind == "average"
            and not sy.sync_dtype and sy.compression.is_identity()
            and sy.asynchrony.is_identity())


def sync_plan(params, spec: EngineSpec, mesh=None) -> dict:
    """Which synced leaves the one-pass sync kernel takes and which keep the
    jnp sync in a round step built for ``mesh`` (as ``build_round_step``),
    decided by each leaf's shape and dtype (``kops.sync_tiles``).

    ``params`` is one replica's tree (arrays or ShapeDtypeStructs, no M
    dim). Returns the '/'-joined paths under ``kernel`` and ``jnp``, and
    ``kernel_bytes`` / ``jnp_bytes``: one replica's bytes of each synced
    tree (the params, and the momentum when it is averaged). Personal leaves
    are not synced and appear in neither."""
    from repro.kernels import ops as kops
    one_pass = _one_pass(spec, mesh)
    plan = {"kernel": [], "kernel_bytes": 0, "jnp": [], "jnp_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if _is_personal(spec.sync.personal, path):
            continue
        side = "kernel" if one_pass and kops.sync_tiles(
            (1,) + tuple(leaf.shape), leaf.dtype) else "jnp"
        plan[side].append(_path_str(path))
        plan[side + "_bytes"] += math.prod(leaf.shape) \
            * jnp.dtype(leaf.dtype).itemsize
    return plan


def _sync_one_pass(tree_m, w_part, avg, personal: tuple, drift: bool):
    """The averaging server's sync of an ``(M, ...)`` client tree: each leaf
    that tiles is read once by the kernel, which averages it and sums its
    drift; the other leaves take the jnp average and broadcast-back, and
    personal leaves keep each client's value. Returns ``(tree,
    client_drift(tree_m) | None)``."""
    from repro.kernels import ops as kops
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_m)
    out, drifts = [], []
    for path, p in flat:
        if _is_personal(personal, path):
            out.append(p)
            drifts.append(_leaf_drift(p) if drift else None)
        elif kops.sync_tiles(p.shape, p.dtype):
            # a leaf with a dim between M and its matrix is a layer stack,
            # which the client loop carries layer-major: the kernel reads it
            # so and XLA broadcasts the average; other leaves are written
            # back in place (kernels/sync_average.py)
            new, d = kops.sync_average(p, w_part, drift=drift,
                                       layer_major=p.ndim >= 4)
            out.append(new)
            drifts.append(d)
        else:
            out.append(jnp.broadcast_to(avg(p)[None], p.shape
                                        ).astype(p.dtype))
            drifts.append(_leaf_drift(p) if drift else None)
    return jax.tree_util.tree_unflatten(treedef, out), \
        (sum(drifts) if drift else None)


# --------------------------------------------------------------------------- #
# ServerUpdate
# --------------------------------------------------------------------------- #


def _compress_server_state(spec: ServerSpec, m, v):
    """Compress the server m/v trees for the replica-agreement sync leg
    (arXiv:2109.05109): the adaptive state every replica must share is kept
    in its compressed form, so the per-round server-state traffic stops
    scaling with the full fp32 trees (``bytes_on_wire``'s
    ``server_state_bytes``). ``sync_k`` keeps ONE shared largest-|m| index
    set per leaf for both trees — a dropped coordinate contributes no step
    and its v falls back to the ``v_init`` floor, preserving the τ²
    adaptivity floor semantics; ``sync_dtype`` QDQ-round-trips both trees
    behind optimization barriers (same discipline as the sync average)."""
    if spec.sync_k < 1.0:
        v0 = spec.v_init if spec.v_init is not None else spec.tau ** 2

        def mask_leaf(mm):
            fm = mm.reshape(-1)
            kc = _k_count(spec.sync_k, fm.size)
            idx = jax.lax.top_k(jnp.abs(fm), kc)[1]
            return jnp.zeros(fm.shape, jnp.bool_).at[idx].set(True) \
                .reshape(mm.shape)

        masks = jax.tree.map(mask_leaf, m)
        m = jax.tree.map(lambda mm, ma: jnp.where(ma, mm, 0.0), m, masks)
        v = jax.tree.map(
            lambda vv, ma: jnp.where(ma, vv, jnp.asarray(v0, vv.dtype)),
            v, masks)
    if spec.sync_dtype:
        sd = jnp.dtype(spec.sync_dtype)
        qdq = lambda a: jax.lax.optimization_barrier(a.astype(sd)) \
            .astype(a.dtype)
        m = jax.tree.map(qdq, m)
        v = jax.tree.map(qdq, v)
    return m, v


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
    if not spec.sync_identity():
        m, v = _compress_server_state(spec, m, v)
    x = jax.tree.map(
        lambda x_, m_, v_: x_ + spec.eta * m_ / (jnp.sqrt(v_) + spec.tau),
        x_prev, m, v)
    return x, {"m": m, "v": v}


# --------------------------------------------------------------------------- #
# The round
# --------------------------------------------------------------------------- #


def build_round_step(loss_fn: Callable, spec: EngineSpec, objective=None,
                     mesh=None):
    """loss_fn(params, microbatch) -> scalar.

    Returns ``round_step(state, batch, key)`` where each batch leaf is
    (M, H, ...): H microbatches per client per round. Returns (state, metrics).
    Metrics: loss, loss_per_client, client_drift (+ step_norm for adaptive
    servers).

    ``objective`` (optional ``objectives.ClientObjective``) replaces the
    differentiated client loss with a semi-supervised one (DESIGN.md §12);
    ``None`` or an identity (supervised) objective leaves every code path —
    and the emitted program — bit-exactly as before. ``spec.sync.personal``
    names client-resident leaves: those never enter the sync average, the
    delta/compression/EF/buffer pipeline, the adaptive server, or the
    broadcast-back — each client keeps its own copy, like the per-client D
    under local scaling. Personalizing D itself therefore requires
    ``scaling="local"`` (or an identity preconditioner): a GLOBAL D is by
    definition shared state, so combining it with a personalization mask is
    a build-time error rather than a silent wire leak.

    ``mesh`` is the device mesh the step is built for (the launch layer's);
    ``None`` means one device. On one device the averaging server's plain
    sync runs as one Pallas pass per leaf that tiles (``sync_plan``); on a
    mesh of several devices it stays in jnp, for GSPMD to partition.
    """
    grad_fn = jax.value_and_grad(loss_fn)
    cl, sy, sv, pc = spec.client, spec.sync, spec.server, spec.precond
    personal = sy.personal
    if personal and cl.scaling == "global" and pc.kind != "identity":
        raise ValueError(
            "personalization with a GLOBAL preconditioner: the shared D is "
            "updated from cross-client sync gradients, which would leak the "
            "personal leaves' gradients over the wire. Use scaling='local' "
            "(per-client D, never synced) or pc kind='identity'.")
    strip = lambda t: strip_personal(personal, t)
    one_pass = _one_pass(spec, mesh)
    client_run = _client_loop(loss_fn, grad_fn, spec, objective=objective)
    ctrl = spec.controller
    if ctrl.enabled:
        # the controller owns the knobs it schedules — conflicting static
        # settings are build-time errors, not silent overrides
        if cl.local_steps is not None:
            raise ValueError("controller and static local_steps are "
                             "exclusive: the controller owns H_m")
        if sy.participation < 1.0:
            raise ValueError("controller requires full participation: its "
                             "gradient-noise estimate needs every client's "
                             "delta (and skipped stragglers are rescaled as "
                             "the sampled subset)")
        if ctrl.buffer_max > 0 and \
                sy.asynchrony.buffer_rounds != ctrl.buffer_max:
            raise ValueError(
                f"controller buffer_max={ctrl.buffer_max} must equal the "
                f"allocated AsyncSpec.buffer_rounds="
                f"{sy.asynchrony.buffer_rounds} (b_eff masks within the "
                f"static FIFO)")

    def round_step(state, batch, key):
        M = jax.tree.leaves(state["params"])[0].shape[0]
        H = jax.tree.leaves(batch)[0].shape[1]

        # ---- Controller knobs for THIS round (DESIGN.md §10) ---------------
        # read from state["ctrl"] — the compiled program is knob-agnostic
        cstate = h_m_dyn = None
        if ctrl.enabled:
            if ctrl.h_max > H:
                raise ValueError(f"controller h_max={ctrl.h_max} exceeds the "
                                 f"round's H={H} microbatches")
            cstate = state["ctrl"]
            h_m_dyn = cstate["h_m"]

        # ---- ClientLoop: H local steps, vmap over M inside the scan --------
        keys = jax.random.split(key, (H, M))
        micro = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch)  # (H,M,..)
        mom0 = jax.tree.map(jnp.zeros_like, state["mom"]) \
            if cl.reset_momentum else state["mom"]
        if h_m_dyn is not None:
            params_m, mom_m, pstate, last_grads, losses = client_run(
                state["params"], mom0, state["precond"], micro, keys,
                h_m=h_m_dyn)
        else:
            params_m, mom_m, pstate, last_grads, losses = client_run(
                state["params"], mom0, state["precond"], micro, keys)

        with jax.named_scope("sync"):
            if not one_pass:
                drift_pre_sync = client_drift(params_m)

            # ---- Controller observations: raw per-client delta statistics
            ctrl_obs = None
            if ctrl.enabled:
                # synced leaves only: personal deltas are client-resident and
                # must not enter the controller's cross-client noise estimate
                x_ref0 = strip(jax.tree.map(lambda p: p[0], state["params"]))
                d_m = jax.tree.map(lambda p, x: p - x[None], strip(params_m),
                                   x_ref0)
                d2_pc = sum(jnp.sum(jnp.reshape(d * d, (M, -1)), axis=1)
                            for d in jax.tree.leaves(d_m))           # (M,)
                dbar_sq = sum(jnp.vdot(b, b).real for b in jax.tree.leaves(
                    jax.tree.map(lambda d: d.mean(axis=0), d_m)))
                ctrl_obs = {"delta_sq_mean": d2_pc.mean(),
                            "delta_sq_avg": dbar_sq,
                            "payload_sq": jnp.float32(0.0),
                            "resid_sq": jnp.float32(0.0)}

            # ---- SyncStrategy: the only cross-client traffic per round -----
            avg = make_sync(sy, key, M)
            comp, asy = sy.compression, sy.asynchrony
            new_ef = delta_avg = comp_err = new_buffer = staleness = None
            # every tree below is the SYNCED view: ``strip`` (identity for
            # the empty personalization mask) None-strips the client-resident
            # leaves, so no average / delta / compression / buffer op ever
            # touches them (DESIGN.md §12) — ``params_avg`` is a synced-leaf
            # tree recombined with the untouched personal leaves at
            # broadcast-back
            if one_pass:
                # average and drift in one read of each leaf that tiles
                # (kernels/sync_average.py); at M=2 the average is bitwise
                # the jnp sync's
                w_part = participation_weights(sy, key, M)
                params_m, drift_pre_sync = _sync_one_pass(
                    params_m, w_part, avg, personal, drift=True)
                if sy.average_momentum:
                    mom_m, _ = _sync_one_pass(mom_m, w_part, avg, personal,
                                              drift=False)
                params_avg = jax.tree.map(lambda x: x[0], params_m)
            elif comp.is_identity() and asy.is_identity():
                # bit-for-bit the uncompressed synchronous program (DESIGN.md
                # §4/§5 contract) — no delta reconstruction, no
                # residual/buffer state
                params_avg = jax.tree.map(avg, strip(params_m))
            else:
                # delta form: Δ_m = x_{m,H} − x_t (clients start each round
                # at the common broadcast point, so x_t = params[0])
                x_ref = strip(jax.tree.map(lambda p: p[0], state["params"]))
                u_m = jax.tree.map(lambda p, x: p - x[None], strip(params_m),
                                   x_ref)
                if comp.is_identity():
                    c_m = u_m
                else:
                    if comp.error_feedback:
                        u_m = jax.tree.map(jnp.add, u_m, state["ef"])
                    k_dyn = cstate["k"] if (ctrl.enabled
                                            and comp.op in ("topk", "randk")) \
                        else None
                    c_m = compress_tree(comp, u_m, key, k_frac=k_dyn)
                    if comp.error_feedback:
                        new_ef = jax.tree.map(jnp.subtract, u_m, c_m)
                    comp_err = sum(jnp.vdot(u - c, u - c).real for u, c in zip(
                        jax.tree.leaves(u_m), jax.tree.leaves(c_m)))
                    if ctrl_obs is not None:
                        # the compressor's actual input/residual energies feed
                        # the controller's EF-residual-norm guard
                        ctrl_obs["payload_sq"] = sum(
                            jnp.vdot(u, u).real for u in jax.tree.leaves(u_m))
                        ctrl_obs["resid_sq"] = comp_err
                delta_avg = jax.tree.map(avg, c_m)
                if ctrl.enabled and ctrl.buffer_max > 0:
                    # controller-skipped stragglers (h_m = 0) contributed
                    # Δ = 0: rescale the mean to the reporting subset, exactly
                    # the 1/n_part weighting of FedAvg client sampling
                    n_act = jnp.maximum(
                        jnp.sum((h_m_dyn > 0).astype(jnp.float32)), 1.0)
                    delta_avg = jax.tree.map(
                        lambda d: d * (M / n_act).astype(d.dtype), delta_avg)
                if not asy.is_identity():
                    # FedBuff-style staleness buffer (DESIGN.md §5): enqueue
                    # the fresh aggregated delta, apply the staleness-weighted
                    # combination of the FIFO
                    b_eff = cstate["b_eff"] if (
                        ctrl.enabled and ctrl.buffer_max > 0) else None
                    w = staleness_weights(asy, state["round"], b_eff=b_eff)
                    new_buffer = jax.tree.map(
                        lambda b, d: jnp.concatenate(
                            [d[None].astype(b.dtype), b[:-1]], axis=0),
                        state["buffer"], delta_avg)
                    delta_avg = jax.tree.map(
                        lambda b: jnp.tensordot(w.astype(b.dtype), b, axes=1),
                        new_buffer)
                    staleness = jnp.sum(
                        w * jnp.arange(asy.buffer_rounds, dtype=jnp.float32))
                params_avg = jax.tree.map(
                    lambda x, d: x + d.astype(x.dtype), x_ref, delta_avg)

            if sv.kind == "average" and not one_pass:
                # personal leaves keep each client's own value (no broadcast)
                params_m = _broadcast_back(params_m, params_avg)
                params_avg = jax.tree.map(lambda x: x[0], params_m)
                if sy.average_momentum:
                    mom_m = _merge_personal(
                        strip(mom_m), mom_m,
                        lambda s, m: jnp.broadcast_to(
                            avg(s)[None], m.shape).astype(m.dtype))

        with jax.named_scope("server"):
            # ---- D update at sync (global scaling; Algorithm 1 line 4) -----
            if cl.scaling == "global" and pc.kind != "identity":
                g_last = last_grads  # (M, ...) — grads of the sync step
                if cl.stat_source == "avg_grad":
                    # participation+dtype apply
                    g_avg = jax.tree.map(avg, g_last)
                    if pc.uses_hutchinson:
                        sync_micro = jax.tree.map(lambda x: x[-1, 0], micro)
                        stat = PC.hutchinson_diag(
                            loss_fn, params_avg, sync_micro,
                            jax.random.fold_in(key, 7))
                    elif pc.rule == "linear":
                        stat = jax.tree.map(jnp.abs, g_avg)
                    else:
                        stat = PC.grad_stat(g_avg)
                else:  # avg_local
                    if pc.uses_hutchinson:
                        # (M, ...)
                        sync_micro = jax.tree.map(lambda x: x[-1], micro)
                        hk = jax.random.split(jax.random.fold_in(key, 7), M)
                        stats = jax.vmap(lambda p, mc, k: PC.hutchinson_diag(
                            loss_fn, p, mc, k))(params_m, sync_micro, hk)
                    elif pc.rule == "linear":
                        stats = jax.tree.map(jnp.abs, g_last)
                    else:
                        stats = PC.grad_stat(g_last)
                    stat = jax.tree.map(lambda s: s.mean(axis=0), stats)
                pstate = PC.update(pc, pstate, stat)

            if h_m_dyn is not None or _needs_masking(cl, H, M):
                # heterogeneous H_m: steps past a client's budget froze its
                # state; average only the executed steps, and report each
                # client's loss at ITS final step H_m−1, not the global step
                # H−1. (For a controller-skipped client, H_m = 0, its rows
                # drop from the mean and the clamped index reports its frozen
                # round-start loss.)
                h_m = h_m_dyn if h_m_dyn is not None \
                    else jnp.asarray(cl.local_steps, jnp.int32)
                act = jnp.arange(H, dtype=jnp.int32)[:, None] < h_m[None, :]
                loss_mean = jnp.sum(losses * act) / jnp.maximum(
                    jnp.sum(act), 1)
                loss_per_client = jnp.take_along_axis(
                    losses, jnp.maximum(h_m - 1, 0)[None, :], axis=0)[0]
            else:
                loss_mean = losses.mean()
                loss_per_client = losses[-1]
            metrics = {
                "loss": loss_mean,
                "loss_per_client": loss_per_client,
                "client_drift": drift_pre_sync,
            }
            if comp_err is not None:
                # Σ‖u_m − C(u_m)‖²
                metrics["compression_err"] = comp_err
            if staleness is not None:
                # E_w[τ] of the applied delta
                metrics["staleness"] = staleness
            if ctrl.enabled:
                # realized knobs of THIS round + the raw observations, so a
                # numpy replay (tests/_reference_controller.py) can reproduce
                # the whole trajectory from logs alone
                metrics["ctrl_h_m"] = h_m_dyn
                metrics["ctrl_h_t"] = cstate["h_t"]
                metrics["ctrl_k"] = cstate["k"]
                # 0 = depth not managed by the controller
                metrics["ctrl_b_eff"] = cstate["b_eff"] \
                    if ctrl.buffer_max > 0 else jnp.int32(0)
                metrics["delta_sq_mean"] = ctrl_obs["delta_sq_mean"]
                metrics["delta_sq_avg"] = ctrl_obs["delta_sq_avg"]
                metrics["payload_sq"] = ctrl_obs["payload_sq"]

            # ---- ServerUpdate -----------------------------------------------
            new_state = {"round": state["round"] + 1, "precond": pstate}
            if new_ef is not None:
                new_state["ef"] = new_ef
            if new_buffer is not None:
                new_state["buffer"] = new_buffer
            if ctrl.enabled:
                # roll the knobs forward for the NEXT round (pure, jit-traced;
                # checkpointing the state pytree checkpoints the controller)
                new_cstate, _ = CTRL.controller_step(ctrl, cstate, ctrl_obs)
                new_state["ctrl"] = new_cstate
                metrics["ctrl_gns_ema"] = new_cstate["gns_ema"]
            if sv.kind == "adaptive":
                x_prev = strip(jax.tree.map(lambda p: p[0], state["params"]))
                if delta_avg is not None:
                    # compressed path: Δ is exactly the averaged compressed
                    # delta (params_avg = x_prev + Δ would re-add/re-subtract
                    # x_prev)
                    delta = jax.tree.map(
                        lambda d, x: d.astype(x.dtype), delta_avg, x_prev)
                else:
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
