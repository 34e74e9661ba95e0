"""End-to-end training driver for every engine method.

Two launch paths share one spec resolution, data pipeline, round loop and
checkpoint format (DESIGN.md §9):

* ``--mesh none`` (default) — single-host ``jax.jit`` over the engine's
  round step; runs anywhere, used by the CPU examples and tests.
* ``--mesh production|production-2pod|debug`` — the launch-layer path:
  ``steps.build_train_step`` builds the jitted step with the mesh plan's
  shardings and donation (paper / paper_fsdp / plain modes, DESIGN.md
  §2). The plan fixes the client count M (e.g. 16 on the 16×16
  production mesh in paper mode);
  ``--clients`` applies to the single-host path only. Production meshes
  on CPU need ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
  set before jax initializes (see launch/dryrun.py).

Determinism and resume (DESIGN.md §9): the per-round key is
``fold_in(PRNGKey(seed+1), r)`` on both paths (the mesh step folds the
carried ``state["round"]`` counter), data is round-addressable
(``LMRoundLoader.round_batch(r, ...)``), and modal stubs are seeded from
(seed, round) — so train(T) ≡ train(t) + restore + train(T−t) bitwise in
loss, state, and every log field except the wall-clock measurements.

``--method`` selects the round composition (ClientLoop × SyncStrategy ×
ServerUpdate, see core/engine.py): savic (Algorithm 1), the FedOpt baselines
of [42] (fedadagrad / fedadam / fedyogi), and the composed local-adam
scenario (locally-scaled clients + adaptive server, cf. 2409.13155).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --rounds 20 --h-local 4 --clients 4 --batch 8 --seq 128 \
      --preconditioner adam --scaling global --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --method local-adam --rounds 5 --clients 2 --batch 2 --seq 64
  XLA_FLAGS=--xla_force_host_platform_device_count=512 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --mesh production --batch 16 --seq 4096
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_lib
from repro.configs import ShapeConfig, get_config
from repro.core import PrecondConfig, SavicConfig, engine, objectives, savic
from repro.data import LMRoundLoader, TokenStream
from repro.data import federated
from repro.models import ModelCallConfig, build
from repro.models.layers import head_path
from repro.utils.compile_cache import enable_compile_cache


PROFILE_ROUNDS = 3      # rounds traced by --profile-dir


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers, widths kept "
                         "(0 = the config's depth); sizes a published-width "
                         "model to one chip's memory")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--h-local", type=int, default=4)
    ap.add_argument("--clients", type=int, default=4,
                    help="client count M (single-host path; mesh plans fix M "
                         "from the client axes)")
    ap.add_argument("--batch", type=int, default=8, help="per-client batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-rounds", type=int, default=0,
                    help="train round r on data round r mod N: the clients "
                         "revisit N rounds of data (0 = fresh data every "
                         "round)")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "debug", "production", "production-2pod"],
                    help="route the launch through steps.build_train_step on "
                         "this mesh (none = single-host jax.jit fallback)")
    ap.add_argument("--mesh-shape", default="2x2",
                    help="data×model shape for --mesh debug, e.g. 1x1 / 2x4")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "paper", "paper_fsdp", "plain", "diloco"],
                    help="mesh axis plan (auto: plain for BIG_ARCHS else "
                         "paper; see DESIGN.md §2)")
    ap.add_argument("--method", default="savic", choices=list(engine.METHODS))
    ap.add_argument("--preconditioner", default="adam",
                    choices=["identity", "adam", "rmsprop", "oasis",
                             "adahessian", "adagrad"])
    ap.add_argument("--scaling", default="global", choices=["global", "local"])
    ap.add_argument("--gamma", type=float, default=3e-3,
                    help="client step size (γ / η_l)")
    ap.add_argument("--beta1", type=float, default=0.9,
                    help="client heavy-ball momentum (savic/local-adam)")
    ap.add_argument("--alpha", type=float, default=1e-2)
    ap.add_argument("--server-eta", type=float, default=0.1,
                    help="adaptive-server lr η (fed*/local-adam)")
    ap.add_argument("--server-beta1", type=float, default=0.9,
                    help="adaptive-server momentum β₁ (fed*/local-adam)")
    ap.add_argument("--tau", type=float, default=1e-3,
                    help="adaptive-server floor τ (fed*/local-adam)")
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--sync-dtype", default="")
    ap.add_argument("--compression", default="none",
                    choices=list(engine.COMPRESSION_OPS),
                    help="client->server delta compression (engine-level: "
                         "applies to every method)")
    ap.add_argument("--compression-k", type=float, default=0.1,
                    help="kept fraction per leaf for topk/randk")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry the EF residual buffer in the state pytree")
    ap.add_argument("--het-model", default="uniform",
                    choices=list(federated.SYSTEMS_MODELS),
                    help="systems-heterogeneity model for per-client local "
                         "steps H_m (engine-level: applies to every method)")
    ap.add_argument("--het-sigma", type=float, default=0.6,
                    help="lognormal straggler sigma for --het-model lognormal")
    ap.add_argument("--het-seed", type=int, default=0)
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="server staleness buffer depth B (0 = synchronous)")
    ap.add_argument("--staleness-weight", default="constant",
                    choices=list(engine.STALENESS_WEIGHTINGS),
                    help="staleness weighting s(tau) for the delta FIFO")
    ap.add_argument("--controller", action="store_true",
                    help="adaptive communication-budget controller "
                         "(DESIGN.md §10): gradient-noise-driven H_m growth, "
                         "EF-residual-guarded compression k, straggler-"
                         "spread-selected buffer depth. Owns H_m (the "
                         "--het-model trace feeds its step_times); state "
                         "rides the checkpoint bitwise")
    ap.add_argument("--ctrl-h-min", type=int, default=1,
                    help="controller: initial global local-step budget H_t")
    ap.add_argument("--ctrl-noise-target", type=float, default=1.0,
                    help="controller: grow H_t while the gradient-noise EMA "
                         "exceeds this")
    ap.add_argument("--ctrl-k-min", type=float, default=0.05,
                    help="controller: floor of the compression-k schedule")
    ap.add_argument("--ctrl-resid-guard", type=float, default=0.5,
                    help="controller: EF-residual-norm ratio above which k "
                         "grows back toward 1")
    ap.add_argument("--objective", default="supervised",
                    choices=list(objectives.OBJECTIVES),
                    help="client objective (DESIGN.md §12): supervised is the "
                         "identity (bit-exact pre-objectives program); "
                         "consistency / pseudo-label are the semi-supervised "
                         "losses over the labeled subset")
    ap.add_argument("--labeled-frac", type=float, default=1.0,
                    help="fraction of each client's sequences carrying labels "
                         "(<1 attaches the per-sequence 'labeled' mask leaf)")
    ap.add_argument("--unlabeled-weight", type=float, default=1.0,
                    help="λ_u on the unlabeled objective term")
    ap.add_argument("--pseudo-threshold", type=float, default=0.9,
                    help="confidence gate for --objective pseudo-label")
    ap.add_argument("--personalize", default="",
                    help="comma-separated param-path substrings kept client-"
                         "resident (never synced/served; e.g. 'final_norm'). "
                         "Personalizing under a GLOBAL non-identity D is "
                         "rejected at build time (DESIGN.md §12)")
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of the PROFILE_ROUNDS rounds "
                         "after the compile round to this directory: each "
                         "round a step 'round', with the host spans "
                         "make_batch, put_batch, dispatch, read_loss")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    return ap


def _make_mesh(args):
    if args.mesh == "none":
        return None
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    if args.mesh == "debug":
        shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        return make_debug_mesh(shape)
    return make_production_mesh(multi_pod=args.mesh == "production-2pod")


def _resolve_spec(args, n_clients):
    """CLI knobs -> (EngineSpec, local_steps, step_times); shared by the mesh
    and single-host paths so both train the identical round composition."""
    comp = engine.CompressionSpec(op=args.compression, k=args.compression_k,
                                  error_feedback=args.error_feedback)
    asy = engine.AsyncSpec(buffer_rounds=args.async_buffer,
                           weighting=args.staleness_weight)
    local_steps = None
    step_times = federated.sample_step_times(
        args.het_model, n_clients, seed=args.het_seed, sigma=args.het_sigma)
    ctrl = None
    if args.controller:
        # the controller owns H_m — no static local_steps bake; the sampled
        # straggler trace is its observed spread (DESIGN.md §10)
        ctrl = engine.ControllerSpec(
            enabled=True, h_min=args.ctrl_h_min, h_max=args.h_local,
            noise_target=args.ctrl_noise_target, k_min=args.ctrl_k_min,
            resid_guard=args.ctrl_resid_guard,
            buffer_max=args.async_buffer,
            step_times=tuple(float(t) for t in step_times))
    elif args.het_model != "uniform":
        local_steps = tuple(int(h) for h in federated.local_steps_from_times(
            step_times, args.h_local))
    if args.method == "savic":
        pc = PrecondConfig(kind=args.preconditioner, alpha=args.alpha)
        sv = SavicConfig(gamma=args.gamma, beta1=args.beta1,
                         scaling=args.scaling,
                         participation=args.participation,
                         sync_dtype=args.sync_dtype,
                         compression=comp, local_steps=local_steps,
                         asynchrony=asy)
        spec = savic.engine_spec(pc, sv)
    else:
        spec = engine.method_spec(
            args.method, pc_kind=args.preconditioner, alpha=args.alpha,
            beta1=args.beta1, eta=args.server_eta, eta_l=args.gamma,
            tau=args.tau, server_beta1=args.server_beta1,
            participation=args.participation,
            sync_dtype=args.sync_dtype, compression=comp,
            local_steps=local_steps, asynchrony=asy)
    if ctrl is not None:
        import dataclasses as _dc
        spec = _dc.replace(spec, controller=ctrl)
    personal = tuple(p for p in args.personalize.split(",") if p)
    if personal:
        import dataclasses as _dc
        spec = _dc.replace(spec, sync=_dc.replace(spec.sync,
                                                  personal=personal))
    return spec, local_steps, step_times


def _objective_spec(args) -> objectives.ObjectiveSpec:
    return objectives.ObjectiveSpec(
        kind=args.objective, unlabeled_weight=args.unlabeled_weight,
        pseudo_threshold=args.pseudo_threshold)


class TrainLog(list):
    """The per-round records ``main`` returns, plus ``setup``: the device,
    the model's depth, and the round step's compile time, compiled memory
    and Pallas kernel count (``_compile``), and the devices' peak bytes."""

    def __init__(self, setup):
        super().__init__()
        self.setup = setup


def _compile(step, args):
    """AOT-compile the round step, so round 0's wall time is the round's
    own and compilation is reported as set-up."""
    t = time.perf_counter()
    compiled = step.lower(*args).compile()
    info = {"compile_s": time.perf_counter() - t}
    ma = compiled.memory_analysis()
    if ma is not None:
        info.update(argument_bytes=ma.argument_size_in_bytes,
                    output_bytes=ma.output_size_in_bytes,
                    alias_bytes=ma.alias_size_in_bytes,
                    temp_bytes=ma.temp_size_in_bytes,
                    peak_bytes=ma.peak_memory_in_bytes)
    info["pallas_calls"] = compiled.as_text().count("tpu_custom_call")
    return compiled, info


def main(argv=None):
    args = _parser().parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[train] device {dev.platform} {dev.device_kind} "
          f"x{jax.device_count()}", flush=True)
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    log = TrainLog({"device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": jax.device_count()},
                    "n_layers": cfg.n_layers})
    call = ModelCallConfig(dtype=getattr(jnp, args.dtype))
    mesh = _make_mesh(args)

    if mesh is not None:
        from repro.launch import steps as steps_mod
        plan, plan_mode = steps_mod._train_plan(args.arch, mesh, args.mode)
        M = plan.clients(mesh) if plan.client else 1
        if M != args.clients:
            print(f"[train] mesh plan '{plan_mode}' fixes M={M} clients "
                  f"(--clients {args.clients} ignored)", flush=True)
    else:
        M = args.clients

    spec, local_steps, step_times = _resolve_spec(args, M)
    model = build(cfg, call)

    params_one = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    wire = engine.bytes_on_wire(spec, params_one)
    print(f"[train] sync payload/client/round: {wire['total_bytes']/1e6:.3f} "
          f"MB ({wire['compression_x']}x vs uncompressed)", flush=True)
    sim_t = federated.simulated_round_time(
        step_times, local_steps or [args.h_local] * M,
        barrier="async" if args.async_buffer else "sync",
        buffer_rounds=args.async_buffer)
    if args.het_model != "uniform" or args.async_buffer:
        print(f"[train] het={args.het_model} H_m="
              f"{list(local_steps) if local_steps else 'uniform'} "
              f"buffer={args.async_buffer} simulated round time {sim_t:.3f} "
              f"(rel. units)", flush=True)

    if mesh is not None:
        shape = ShapeConfig(f"train_cli_{args.seq}", args.seq,
                            M * args.batch, "train")
        built = steps_mod.build_train_step(
            args.arch, shape, mesh, mode=args.mode, engine_spec=spec,
            reduced=args.reduced, h_local=args.h_local, call=call,
            objective=_objective_spec(args), labeled_frac=args.labeled_frac,
            seed=args.seed + 1, n_layers=args.layers or None)
        state_shardings, batch_shardings = built.in_shardings
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate)
        print(f"[train] mesh {dict(mesh.shape)} mode={built.meta['mode']} "
              f"M={M} b_client={args.batch} devices={mesh.size}", flush=True)
        step_args = lambda state, batch, r: (state, batch)
        put_batch = lambda nb: jax.device_put(nb, batch_shardings)
    else:
        client_obj = objectives.build_objective(_objective_spec(args),
                                                model=model)
        # the state is donated, as on the mesh path: without it the old and
        # the new state are live at once (7.5 GB each for 16 layers of
        # qwen2-0.5b at M=2, which with the temporaries overflows a v5e)
        jitted = jax.jit(engine.build_round_step(model.loss, spec,
                                                 objective=client_obj),
                         donate_argnums=0)
        root = jax.random.PRNGKey(args.seed + 1)
        # fold_in(root, r), NOT sequential splits from process start: a
        # restored run replays exactly round r's key (DESIGN.md §9)
        step_args = lambda state, batch, r: (
            state, batch, jax.random.fold_in(root, r))
        put_batch = lambda nb: jax.tree.map(jnp.asarray, nb)

    # which synced leaves the one-pass sync kernel takes (one device only)
    plan = engine.sync_plan(params_one, spec, mesh)
    log.setup["sync_plan"] = {k: len(v) if isinstance(v, list) else v
                              for k, v in plan.items()}
    print(f"[train] sync: one-pass kernel {len(plan['kernel'])} leaves "
          f"{plan['kernel_bytes'] / 1e6:.3f} MB, jnp {len(plan['jnp'])} "
          f"leaves {plan['jnp_bytes'] / 1e6:.3f} MB (one replica's tree)",
          flush=True)
    # the loss's LM head: the fused kernel on one TPU device, else jnp
    log.setup["head"] = built.meta["head"] if mesh is not None else \
        head_path(cfg, args.batch * args.seq)
    print(f"[train] lm head: {log.setup['head']}", flush=True)

    state = engine.init_state(jax.random.PRNGKey(args.seed), model.init, spec,
                              M)
    start_round = 0
    if args.ckpt and ckpt_lib.latest_step(args.ckpt) is not None:
        state, start_round = ckpt_lib.restore(args.ckpt, state)
        print(f"[train] restored round {start_round}")
    if mesh is not None:
        state = jax.device_put(state, state_shardings)

    stream = TokenStream(cfg.vocab_size, seed=args.seed)
    loader = LMRoundLoader(stream, M, args.batch,
                           labeled_frac=args.labeled_frac, seed=args.seed)
    tokens_round = M * args.h_local * args.batch * args.seq
    compiled = None
    t0 = time.time()
    profiling = False
    with mesh if mesh is not None else contextlib.nullcontext():
        for r in range(start_round, args.rounds):
            if args.profile_dir and r == start_round + 1:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            with jax.profiler.StepTraceAnnotation("round", step_num=r):
                with jax.profiler.TraceAnnotation("make_batch"):
                    nb = loader.round_batch(
                        r % args.data_rounds if args.data_rounds else r,
                        args.h_local, args.seq)
                    if cfg.family in ("audio", "vlm"):
                        nb = _wrap_modal(cfg, nb, args.seed, r)
                with jax.profiler.TraceAnnotation("put_batch"):
                    batch = put_batch(nb)
                call_args = step_args(state, batch, r)
                if compiled is None:
                    compiled, info = _compile(jitted, call_args)
                    log.setup.update(info)
                    print(f"[train] round step compiled in "
                          f"{info['compile_s']:.1f}s: "
                          + "".join(f"{k} {info[k] / 1e9:.3f} GB, "
                                    for k in ("argument_bytes", "temp_bytes",
                                              "peak_bytes") if k in info)
                          + f"{info['pallas_calls']} Pallas calls", flush=True)
                tw = time.perf_counter()
                with jax.profiler.TraceAnnotation("dispatch"):
                    state, metrics = compiled(*call_args)
                with jax.profiler.TraceAnnotation("read_loss"):
                    loss = float(metrics["loss"])      # blocks on the round
                wall = time.perf_counter() - tw
                drift = float(metrics["client_drift"])
                rec = {"round": r, "loss": loss, "drift": drift}
                extra = ""
                if "step_norm" in metrics:
                    rec["step_norm"] = float(metrics["step_norm"])
                    extra = f" step {rec['step_norm']:.3e}"
                if "compression_err" in metrics:
                    rec["compression_err"] = float(metrics["compression_err"])
                if "staleness" in metrics:
                    rec["staleness"] = float(metrics["staleness"])
                if "ctrl_h_m" in metrics:
                    # realized knob trajectory (DESIGN.md §10). Per-round
                    # sim_round_time (not a cumulative) so a resumed run logs
                    # bitwise-identical rounds; consumers sum it themselves.
                    h_real = [int(h) for h in np.asarray(metrics["ctrl_h_m"])]
                    b_real = int(metrics["ctrl_b_eff"])
                    rec["ctrl_h_m"] = h_real
                    rec["ctrl_h_t"] = int(metrics["ctrl_h_t"])
                    rec["ctrl_k"] = round(float(metrics["ctrl_k"]), 6)
                    rec["ctrl_b_eff"] = b_real
                    rec["ctrl_gns_ema"] = round(
                        float(metrics["ctrl_gns_ema"]), 6)
                    extra += f" H_t {rec['ctrl_h_t']}"
                    rec["sim_round_time"] = round(
                        federated.simulated_round_time(
                            step_times, h_real,
                            barrier="async" if args.async_buffer else "sync",
                            buffer_rounds=b_real or args.async_buffer), 4)
                else:
                    # simulated clock
                    rec["sim_time"] = round((r + 1) * sim_t, 4)
                # measurements — the only non-deterministic log fields (§9)
                rec["wall_s"] = round(wall, 4)
                rec["tokens_per_s"] = round(tokens_round / wall, 1)
                log.append(rec)
                print(f"[train] round {r:4d} loss {loss:.4f} drift {drift:.3e}"
                      f"{extra} {wall:.3f}s/round {rec['tokens_per_s']:.0f} "
                      f"tok/s ({time.time()-t0:.1f}s)", flush=True)
                if args.ckpt and (r + 1) % args.ckpt_every == 0:
                    ckpt_lib.save(args.ckpt, r + 1, state)
            if profiling and r == start_round + PROFILE_ROUNDS:
                jax.profiler.stop_trace()
                profiling = False
    if profiling:
        jax.profiler.stop_trace()
    log.setup["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()]
    if args.ckpt:
        ckpt_lib.save(args.ckpt, args.rounds, state)
    if args.log:
        with open(args.log, "w") as f:
            json.dump(log, f)
    return log


def _wrap_modal(cfg, nb, seed, r):
    """audio/vlm batches need embedding/patch stubs around the token stream.

    Seeded from (seed, round): every round draws fresh modal inputs (a fresh
    ``default_rng(0)`` here used to freeze audio/vlm training on ONE batch
    forever), and the same round reproduces bitwise on resume (DESIGN.md §9).
    The trailing 1 separates this stream from TokenStream.batch_at(r)'s.
    """
    rng = np.random.default_rng((seed, r, 1))
    M, H, b, S = nb["tokens"].shape
    lab = {"labeled": nb["labeled"]} if "labeled" in nb else {}
    if cfg.family == "audio":
        emb = rng.normal(size=(M, H, b, S, cfg.d_model)).astype(np.float32) * .02
        return {"embeds": emb, "labels": nb["labels"], **lab}
    P = cfg.frontend_tokens
    # batch_struct contract: P patch embeddings prepended to S−P text tokens,
    # so the model's position budget stays at --seq on both launch paths
    patches = rng.normal(size=(M, H, b, P, cfg.d_model)).astype(np.float32) * .02
    return {"patches": patches, "tokens": nb["tokens"][..., :S - P],
            "labels": nb["labels"][..., :S - P], **lab}


if __name__ == "__main__":
    main()
