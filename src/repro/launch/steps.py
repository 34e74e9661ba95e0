"""Step builders: (arch × input-shape × mesh × mode) -> jit-able function +
abstract inputs + shardings.  Shared by dryrun.py, train.py, serve.py and the
benchmarks.

Shape kinds:
* train   -> SAVIC ``round_step``  (H local steps × M clients + sync)
* prefill -> ``prefill`` (full forward, returns last logits + KV cache)
* decode  -> ``serve_step`` (ONE new token against a seq_len KV cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig, ShapeConfig, get_config, get_shape
from repro.core import PrecondConfig, SavicConfig, engine, objectives, savic
from repro.models import ModelCallConfig, batch_struct, build
from repro.models.layers import head_path
from repro.sharding import (AxisPlan, batch_pspecs, cache_pspecs,
                            params_pspecs, plan_for, serve_batch_pspecs)

# archs whose full replica does not fit a 16-chip model group in fp32 training
# (plain mode: M=1, params FSDP-sharded over the data axis; see DESIGN.md §2)
BIG_ARCHS = ("deepseek-67b", "deepseek-v2-236b")

# decode window (ring-buffer KV) used in the long_500k shape on windowed archs
LONG_DECODE_WINDOW = 8192


@dataclasses.dataclass
class BuiltStep:
    fn: Any                   # jit-able python callable
    args: tuple               # abstract ShapeDtypeStructs (or concrete arrays)
    in_shardings: tuple
    out_shardings: Any
    donate: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)


def _train_plan(arch: str, mesh, mode: str = "auto") -> AxisPlan:
    multi = "pod" in mesh.axis_names
    if mode == "auto":
        mode = "plain" if arch in BIG_ARCHS else "paper"
    return plan_for(mode, multi), mode


def savic_round_h(shape: ShapeConfig) -> int:
    return 8  # local steps per round lowered in the dry-run (scan: HLO-size free)


def _method_engine_spec(method: str, pc_kind: str,
                        sv: Optional[SavicConfig]) -> engine.EngineSpec:
    """Resolve the engine spec for a train-step method selector."""
    if method == "savic":
        pc = PrecondConfig(kind=pc_kind, alpha=1e-2)
        return savic.engine_spec(pc, sv or SavicConfig(gamma=3e-4, beta1=0.9))
    if sv is not None:
        raise ValueError(f"sv= (SavicConfig) only applies to method='savic', "
                         f"got method={method!r}")
    return engine.method_spec(method, pc_kind=pc_kind)


def build_train_step(arch: str, shape: ShapeConfig, mesh, *, mode: str = "auto",
                     method: str = "savic", pc_kind: str = "adam",
                     call: Optional[ModelCallConfig] = None,
                     reduced: bool = False, h_local: Optional[int] = None,
                     sv: Optional[SavicConfig] = None,
                     engine_spec: Optional[engine.EngineSpec] = None,
                     compression: Optional[engine.CompressionSpec] = None,
                     het_model: Optional[str] = None, het_seed: int = 0,
                     het_sigma: float = 0.6,
                     local_steps: Optional[tuple] = None,
                     asynchrony: Optional[engine.AsyncSpec] = None,
                     controller: Optional[engine.ControllerSpec] = None,
                     objective: Optional[objectives.ObjectiveSpec] = None,
                     labeled_frac: float = 1.0,
                     personal: Optional[tuple] = None,
                     seed: int = 0,
                     n_layers: Optional[int] = None):
    cfg = get_config(arch, reduced=reduced)
    if n_layers:                   # depth cut to one chip; widths kept
        cfg = cfg.replace(n_layers=n_layers)
    plan, mode = _train_plan(arch, mesh, mode)
    if call is None:
        call = ModelCallConfig()
    if mode in ("paper_fsdp", "plain") and call.act_shard is None:
        # pin batch-parallel activations (otherwise the d-sharded embedding
        # wins GSPMD propagation and attention replicates; see EXPERIMENTS §Perf)
        # NB: bind the pspec at definition time — `spec` is rebound to the
        # EngineSpec below, and a late-binding closure here handed THAT to
        # NamedSharding (broke every plain-mode build at trace time)
        act_spec = P(tuple(plan.batch), None, None)
        call = dataclasses.replace(
            call, act_shard=lambda x, _s=act_spec:
                jax.lax.with_sharding_constraint(x, NamedSharding(mesh, _s)))
    if cfg.moe and call.moe_shard is None:
        call = dataclasses.replace(
            call, moe_shard=_moe_shard_fn(cfg, mesh, plan))
    call = dataclasses.replace(call, devices=mesh.size)
    model = build(cfg, call)
    M = plan.clients(mesh) if plan.client else 1
    assert shape.global_batch % M == 0, (shape.global_batch, M)
    b_client = shape.global_batch // M
    # the loss's LM head: the fused kernel on one TPU device, else jnp
    head = head_path(cfg, b_client * shape.seq_len, devices=mesh.size)
    H = h_local or savic_round_h(shape)

    spec = engine_spec or _method_engine_spec(method, pc_kind, sv)
    if compression is not None:
        # engine-level knob (like --participation/--sync-dtype): applies to
        # every method, composing with an explicit engine_spec too
        spec = dataclasses.replace(
            spec, sync=dataclasses.replace(spec.sync, compression=compression))
    het_meta = {}
    if het_model is not None and local_steps is None:
        # systems heterogeneity (DESIGN.md §5): sample per-client step times,
        # derive the budgeted H_m vector, record the simulated wall clock
        from repro.data import federated as fed
        step_times = fed.sample_step_times(het_model, M, seed=het_seed,
                                           sigma=het_sigma)
        local_steps = tuple(int(h) for h in
                            fed.local_steps_from_times(step_times, H))
        asy = asynchrony or spec.sync.asynchrony
        het_meta = {
            "het_model": het_model,
            "step_times": [round(float(t), 4) for t in step_times],
            "sim_round_time_sync": round(fed.simulated_round_time(
                step_times, [H] * M, barrier="sync"), 4),
            # budgeted H_m barrier; only an actual staleness buffer makes it
            # an "async" pace (B=0 would mislabel pure H_m budgeting)
            "sim_round_time_budgeted": round(fed.simulated_round_time(
                step_times, local_steps, barrier="sync"), 4),
        }
        if asy.buffer_rounds > 0:
            het_meta["sim_round_time_async"] = round(fed.simulated_round_time(
                step_times, local_steps, barrier="async",
                buffer_rounds=asy.buffer_rounds), 4)
        if controller is not None and controller.enabled \
                and not controller.step_times:
            # the sampled trace IS the controller's observed straggler
            # spread; H_m then comes from the controller, not a static bake
            controller = dataclasses.replace(
                controller,
                step_times=tuple(float(t) for t in step_times))
    if controller is not None and controller.enabled:
        # the controller owns H_m (round-addressable via masking); a static
        # local_steps bake would conflict (build_round_step raises on both)
        local_steps = None
        spec = dataclasses.replace(spec, controller=controller)
        het_meta["controller"] = dataclasses.asdict(controller)
    if local_steps is not None:
        spec = dataclasses.replace(
            spec, client=dataclasses.replace(spec.client,
                                             local_steps=tuple(local_steps)))
    if asynchrony is not None:
        spec = dataclasses.replace(
            spec, sync=dataclasses.replace(spec.sync, asynchrony=asynchrony))
    if personal:
        # client-resident leaves (DESIGN.md §12): engine-level knob like
        # compression/asynchrony — applies to every method / engine_spec
        spec = dataclasses.replace(
            spec, sync=dataclasses.replace(spec.sync,
                                           personal=tuple(personal)))
    client_objective = objectives.build_objective(objective, model=model)
    if client_objective is not None or labeled_frac < 1.0 or personal:
        het_meta["objective"] = {
            "kind": objective.kind if objective is not None else "supervised",
            "labeled_frac": labeled_frac,
            "personal": list(spec.sync.personal),
        }

    # ---- abstract state & batch ----------------------------------------------
    state_shape = jax.eval_shape(
        partial(engine.init_state, init_params_fn=model.init, spec=spec,
                n_clients=M), jax.random.PRNGKey(0))
    micro = batch_struct(cfg, b_client, shape.seq_len)
    if labeled_frac < 1.0:
        # per-SEQUENCE labeled mask emitted by LMRoundLoader(labeled_frac<1);
        # the fully-labeled regime adds no leaf — batch structure (and the
        # compiled program) stay bit-exact pre-objectives
        micro = dict(micro)
        micro["labeled"] = jax.ShapeDtypeStruct((b_client,), jnp.float32)
    batch_shape = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((M, H) + s.shape, s.dtype), micro)

    round_step = engine.build_round_step(model.loss, spec,
                                         objective=client_objective,
                                         mesh=mesh)

    def step(state, batch):
        # per-round key folded from the carried round counter: restart- and
        # resume-invariant by construction (DESIGN.md §9)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), state["round"])
        return round_step(state, batch, key)

    # ---- shardings (see DESIGN.md §2) ----------------------------------------
    state_spec = _engine_state_spec(cfg, state_shape, mesh, plan, spec)
    batch_spec = batch_pspecs(batch_shape, mesh, plan, client_dim=True)
    metrics_shape = jax.eval_shape(step, state_shape, batch_shape)[1]
    metrics_spec = jax.tree.map(lambda _: P(), metrics_shape)
    metrics_spec["loss_per_client"] = P(plan.client if plan.client else None)
    if "ctrl_h_m" in metrics_shape:
        # realized per-client H_m: client-sharded like loss_per_client
        metrics_spec["ctrl_h_m"] = P(plan.client if plan.client else None)

    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    return BuiltStep(
        fn=step,
        args=(state_shape, batch_shape),
        in_shardings=(ns(state_spec), ns(batch_spec)),
        out_shardings=(ns(state_spec), ns(metrics_spec)),
        donate=(0,),
        meta={"mode": mode, "method": method, "clients": M, "h_local": H,
              "b_client": b_client, "cfg": cfg, "plan": plan,
              "engine_spec": spec, "head": head, **het_meta},
    )


def _engine_state_spec(cfg, state_shape, mesh, plan, spec: engine.EngineSpec):
    """PartitionSpec tree for an engine state pytree (DESIGN.md §2): client
    leaves carry a leading M dim over the client axes; the global D and the
    adaptive server's (m, v) are client-replicated single-replica trees.

    Personalization (DESIGN.md §12) needs no special casing for server/buffer
    specs: their shape-trees are already None-stripped by ``init_state`` and
    ``params_pspecs`` walks paths, so the spec trees come out stripped to
    match. Only the ``ef`` spec is derived from the FULL params spec tree and
    must be stripped explicitly (PartitionSpecs are tuples — containers — so
    the strip needs ``is_leaf``)."""
    pspec_m = params_pspecs(cfg, state_shape["params"], mesh, plan,
                            client_dim=True)
    state_spec = {
        "params": pspec_m,
        "mom": pspec_m,
        "precond": _precond_spec(cfg, state_shape["precond"], mesh, plan,
                                 local=spec.client.scaling == "local"),
        "round": P(),
    }
    if "server" in state_shape:
        pspec_1 = params_pspecs(cfg, state_shape["server"]["m"], mesh, plan,
                                client_dim=False)
        state_spec["server"] = {"m": pspec_1, "v": pspec_1}
    if "ef" in state_shape:
        # EF compression residual: per-client, sharded exactly like params/mom
        state_spec["ef"] = engine.strip_personal(
            spec.sync.personal, pspec_m,
            is_leaf=lambda x: isinstance(x, P))
    if "buffer" in state_shape:
        # staleness delta FIFO (DESIGN.md §5): single-replica shaped with a
        # leading B dim — B is never sharded, inner dims like one replica's
        # params (client-replicated server state, like server.m/v)
        buf_one = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            state_shape["buffer"])
        pspec_buf = params_pspecs(cfg, buf_one, mesh, plan, client_dim=False)
        state_spec["buffer"] = jax.tree.map(
            lambda s: P(None, *s), pspec_buf,
            is_leaf=lambda x: isinstance(x, P))
    if "ctrl" in state_shape:
        # controller knobs/EMAs (DESIGN.md §10): scalars replicated; the (M,)
        # h_m vector rides the client axes like the per-client precond t
        cl_ax = plan.client if plan.client else None
        state_spec["ctrl"] = {
            k: (P(cl_ax) if s.ndim else P())
            for k, s in state_shape["ctrl"].items()}
    return state_spec


def _moe_shard_fn(cfg, mesh, plan):
    """Constraint for the (B, E, C, d/f) MoE buffers: batch over batch(+client
    when M=1 plain) axes, experts over model axes when divisible."""
    baxes = tuple(plan.batch) or None
    E = cfg.moe.n_experts
    n_mdl = 1
    for a in plan.model:
        n_mdl *= mesh.shape[a]
    eaxes = tuple(plan.model) if (plan.model and E % n_mdl == 0) else None

    def f(x, where="dispatch"):
        e = eaxes if where == "dispatch" else None
        spec = P(baxes, e, *([None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return f


def _precond_spec(cfg, precond_shape, mesh, plan, local):
    # local scaling keeps a per-client step counter t of shape (M,)
    t_spec = P(plan.client if plan.client else None) \
        if precond_shape["t"].ndim else P()
    spec = {"t": t_spec}
    if "d" in precond_shape:
        # global D: replicated across clients (no client dim), sharded like a
        # single replica's params; local D carries the leading client dim
        spec["d"] = params_pspecs(cfg, precond_shape["d"], mesh, plan,
                                  client_dim=local)
    return spec


def _serve_plan(arch: str, mesh) -> AxisPlan:
    multi = "pod" in mesh.axis_names
    batch = ("pod", "data") if multi else ("data",)
    fsdp = arch in BIG_ARCHS
    return AxisPlan(client=(), batch=batch, model=("model",),
                    fsdp_params=fsdp)


def _serve_call(arch: str, shape: ShapeConfig, call: Optional[ModelCallConfig]):
    if call is not None:
        return call
    window = LONG_DECODE_WINDOW if shape.name == "long_500k" else 0
    return ModelCallConfig(decode_window=window)


def _bf16_params(params_shape):
    """Serving stores weights in bf16 (training keeps fp32 masters)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        params_shape)


def build_prefill_step(arch: str, shape: ShapeConfig, mesh, *,
                       call: Optional[ModelCallConfig] = None,
                       reduced: bool = False,
                       cache_len: Optional[int] = None):
    """Full-sequence prefill on the serve mesh.

    With ``cache_len`` the step is ``model.prefill_cache``: the returned cache
    is in decode layout, populated so a serve_step continues at
    pos = seq_len with no prompt replay (DESIGN.md §8).
    """
    cfg = get_config(arch, reduced=reduced)
    call = call or ModelCallConfig()
    plan = _serve_plan(arch, mesh)
    if cfg.moe and call.moe_shard is None:
        call = dataclasses.replace(call,
                                   moe_shard=_moe_shard_fn(cfg, mesh, plan))
    model = build(cfg, call)

    params_shape = _bf16_params(jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))
    batch_shape = batch_struct(cfg, shape.global_batch, shape.seq_len)
    # labels unused in prefill; keep specs uniform anyway
    pspec = params_pspecs(cfg, params_shape, mesh, plan, client_dim=False)
    bspec = serve_batch_pspecs(batch_shape, mesh, plan)

    if cache_len is not None:
        fn = partial(model.prefill_cache, cache_len=cache_len)
    else:
        fn = model.prefill
    out_shape = jax.eval_shape(fn, params_shape, batch_shape)
    logits_spec = P(tuple(plan.batch), None)
    cache_spec = cache_pspecs(cfg, out_shape[1], mesh, plan)

    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    return BuiltStep(
        fn=fn,
        args=(params_shape, batch_shape),
        in_shardings=(ns(pspec), ns(bspec)),
        out_shardings=(ns(logits_spec), ns(cache_spec)),
        meta={"cfg": cfg, "plan": plan, "cache_len": cache_len},
    )


def build_serve_step(arch: str, shape: ShapeConfig, mesh, *,
                     call: Optional[ModelCallConfig] = None,
                     reduced: bool = False, pos_per_slot: bool = False):
    """ONE-token decode against a seq_len-deep KV cache.

    ``pos_per_slot=True`` makes pos a (B,) vector — every slot of the decode
    ring at its own depth (continuous batching; DESIGN.md §8). The cache stays
    slot-major: batch (slot) dim sharded over the data axes by cache_pspecs,
    so one jitted step serves the whole ring across request churn.
    """
    cfg = get_config(arch, reduced=reduced)
    call = _serve_call(arch, shape, call)
    plan = _serve_plan(arch, mesh)
    if cfg.moe and call.moe_shard is None:
        call = dataclasses.replace(call,
                                   moe_shard=_moe_shard_fn(cfg, mesh, plan))
    model = build(cfg, call)
    B = shape.global_batch

    params_shape = _bf16_params(jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))
    cache_shape = jax.eval_shape(partial(model.init_cache, B, shape.seq_len))
    token_shape = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos_shape = jax.ShapeDtypeStruct((B,) if pos_per_slot else (), jnp.int32)

    def serve_step(params, cache, token, pos):
        return model.decode(params, cache, token, pos)

    pspec = params_pspecs(cfg, params_shape, mesh, plan, client_dim=False)
    cspec = cache_pspecs(cfg, cache_shape, mesh, plan)
    tok_spec = P(tuple(plan.batch)) if B % _ax(mesh, plan.batch) == 0 else P(None)
    logits_spec = P(tok_spec[0] if tok_spec != P(None) else None, None)
    pos_spec = tok_spec if pos_per_slot else P()

    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    return BuiltStep(
        fn=serve_step,
        args=(params_shape, cache_shape, token_shape, pos_shape),
        in_shardings=(ns(pspec), ns(cspec), ns(tok_spec), ns(pos_spec)),
        out_shardings=(ns(logits_spec), ns(cspec)),
        donate=(1,),
        meta={"cfg": cfg, "plan": plan, "pos_per_slot": pos_per_slot,
              "decode_window": call.decode_window},
    )


def _ax(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def build_step(arch: str, shape_name: str, mesh, **kw):
    shape = get_shape(shape_name)
    if shape.kind == "train":
        return build_train_step(arch, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(arch, shape, mesh, **kw)
    return build_serve_step(arch, shape, mesh, **kw)
