"""Benchmark registrations — every bench is a declarative matrix config plus
a ``run(point, ctx) -> rows`` callable registered in benchmarks/matrix.py
(DESIGN.md §11).  One runner expands each matrix deterministically, tags
every row with its full axis coordinates + git_rev + schema version, and
emits BENCH_<name>.json + results/bench/<name>.csv in the uniform row shape.

Registered benches (axes in parentheses):

  fig1            paper Figure 1 (main_frac × method; per-round rows)
  thm1 / thm2     Theorem 1/2 shape validation on quadratics (experiment)
  sec52           §5.2 FedAdaGrad v_{-1} critique (v_init × tau)
  engine          wall-time per round per engine method (method)
  compression     bytes-on-wire × wall-time (method × compression)
  async           sync vs buffered-async vs adaptive controller under the
                  lognormal straggler model (method × arm) — the old
                  ``controller`` subcommand is the arm=controller slice
  comm            analytic sync-vs-DDP communication volume (arch)
  kernels         Pallas kernel µs/call, interpret mode (kernel)
  serve           production decode path (arch × mode)
  train_lm        federated causal-LM rounds through the production driver
                  (method; + full-shape projection rows)

Run benches through the matrix CLI::

  python -m benchmarks.matrix run --bench engine [--select method=savic]
  python -m benchmarks.matrix update-output --bench engine   # no rerun
  python benchmarks/diff.py A.json B.json --check            # cross-PR diff

or through this module's legacy alias CLI (``python benchmarks/run.py
[--only engine,async]``), which prints the ``benchmark,metric,value``
trajectory lines derived from the stored rows.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

if __package__ in (None, ""):  # script style: python benchmarks/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import matrix
from benchmarks.matrix import BenchDef, MatrixConfig, make_row, register


# --------------------------------------------------------------------------- #
# shared helpers
# --------------------------------------------------------------------------- #


def _mlp(n_in, n_classes, width=128):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (n_in, width)) * (n_in ** -0.5),
                "b1": jnp.zeros((width,)),
                "w2": jax.random.normal(k2, (width, n_classes)) * width ** -0.5,
                "b2": jnp.zeros((n_classes,))}

    def loss(params, batch):
        h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, batch["y"][:, None], 1)[:, 0]
        return (logz - gold).mean()

    def acc(params, x, y):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        return float((jnp.argmax(logits, -1) == y).mean())

    return init, loss, acc


def _quad_runner(problem, gamma, H, rounds, kind="identity", alpha=1e-8,
                 seed=0):
    from repro.core import PrecondConfig, SavicConfig, savic
    from repro.data import QuadraticLoader
    Q = jnp.asarray(problem.Q, jnp.float32)
    b = jnp.asarray(problem.b, jnp.float32)

    def loss(params, micro):
        x = params["x"]
        Qm, bm = Q[micro["cid"]], b[micro["cid"]]
        return 0.5 * (x - bm) @ Qm @ (x - bm) + micro["z"] @ x

    pc = PrecondConfig(kind=kind, alpha=alpha)
    sv = SavicConfig(gamma=gamma, beta1=0.0)
    step = jax.jit(savic.build_round_step(loss, pc, sv))
    M, d = problem.b.shape
    state = savic.init_state(jax.random.PRNGKey(seed),
                             lambda k: {"x": jnp.zeros(d)}, pc, sv, M)
    loader = QuadraticLoader(problem, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    xstar = jnp.asarray(problem.x_star(), jnp.float32)
    dists = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        state, _ = step(state, jax.tree.map(jnp.asarray,
                                            loader.round_batch(H)), k)
        x = savic.average_params(state)["x"]
        dists.append(float(jnp.sum((x - xstar) ** 2)))
    return np.asarray(dists)


def _time_round_loop(spec, init, loss, data, parts, rounds, H, M, seed):
    """Shared engine/compression timing loop: wall time per round + analytic
    bytes-on-wire per round (benchmark hygiene: every engine timing record
    carries its communication volume)."""
    from repro.core import engine
    from repro.data import FederatedLoader

    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(seed), init, spec, M)
    loader = FederatedLoader(data.x, data.y.astype(np.int32), parts[:M],
                             batch_size=32, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    times = []
    for r in range(rounds):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H))
        t0 = time.perf_counter()
        state, met = step(state, batch, k)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) * 1e3)
    wire = engine.bytes_on_wire(
        spec, jax.eval_shape(init, jax.random.PRNGKey(seed)))
    # only sampled clients transmit under partial participation (half-up to
    # match engine.participation_weights — round() banker's-rounds 0.5·M)
    n_tx = max(1, int(math.floor(spec.sync.participation * M + 0.5)))
    return {
        "round_ms_first": round(times[0], 3),        # includes compile
        "round_ms_mean": round(float(np.mean(times[1:])), 3),
        "round_ms_p50": round(float(np.median(times[1:])), 3),
        "rounds": rounds,
        "final_loss": round(float(met["loss"]), 4),
        "wire_bytes_per_client_round": wire["total_bytes"],
        "wire_bytes_per_round": wire["total_bytes"] * n_tx,
        "compression_x": wire["compression_x"],
    }


def _time(f, *args, n=5):
    r = f(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def _cls_data(ctx, seed, n=2000):
    """Reduced fig1-style classification task, cached across matrix points."""
    key = ("cls_data", seed, n)
    if key not in ctx:
        from repro.data import ClassificationData, main_class_partition
        data = ClassificationData.make(n=n, n_classes=10, seed=seed)
        parts = main_class_partition(data.y, 10, 0.5, seed=seed)
        ctx[key] = (data, parts)
    return ctx[key]


def _extra(ctx, **kv):
    ctx.setdefault("config_extra", {}).update(kv)


def _uniq(doc, axis):
    out = []
    for r in doc["rows"]:
        v = r["coords"][axis]
        if v not in out:
            out.append(v)
    return out


# --------------------------------------------------------------------------- #
# fig1 — the paper's experiment (main_frac × method; per-round rows)
# --------------------------------------------------------------------------- #


FIG1_METHODS = {
    "SGD": ("identity", "global"),
    "Adam global": ("adam", "global"),
    "Adam local": ("adam", "local"),
    "OASIS global": ("oasis", "global"),
    "OASIS local": ("oasis", "local"),
}


def _run_fig1(point, ctx):
    from repro.core import PrecondConfig, SavicConfig, engine, savic
    from repro.data import (ClassificationData, FederatedLoader,
                            main_class_partition)

    f, seed = point.fixed, point.seed
    if "fig1_env" not in ctx:
        data = ClassificationData.make(n=8000, n_classes=10, seed=seed)
        ntest = 1000
        ctx["fig1_env"] = dict(
            data=data, ntest=ntest, parts={},
            xte=jnp.asarray(data.x[-ntest:]), yte=jnp.asarray(data.y[-ntest:]))
    env = ctx["fig1_env"]
    data, ntest = env["data"], env["ntest"]
    frac, mname = point.coords["main_frac"], point.coords["method"]
    if frac not in env["parts"]:
        env["parts"][frac] = main_class_partition(data.y[:-ntest], 10, frac,
                                                  seed=seed)
    kind, scaling = FIG1_METHODS[mname]
    init, loss, acc = _mlp(data.x.shape[1], 10)
    # α floor active (corrected Adam debias: D̂ tracks |g| from the first
    # sync), shared γ across methods — the Fig.1 comparison
    pc = PrecondConfig(kind=kind, alpha=1e-2)
    sv = SavicConfig(gamma=0.002, beta1=0.9, scaling=scaling)
    spec = savic.engine_spec(pc, sv)
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(seed), init, spec,
                              f["clients"])
    loader = FederatedLoader(data.x[:-ntest], data.y[:-ntest].astype(np.int32),
                             env["parts"][frac], batch_size=64, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    rows = []
    for r in range(f["rounds"]):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(f["h_local"]))
        state, met = step(state, batch, k)
        avg = engine.average_params(state)
        rows.append(make_row({**point.coords, "round": r},
                             {"loss": float(met["loss"]),
                              "test_acc": acc(avg, env["xte"], env["yte"])}))
    return rows


def _sum_fig1(doc):
    # convergence SPEED (the paper's Fig.1 axis is communication rounds):
    # rounds to reach loss <= 1.2 and loss at round 10, per method
    out = []
    rows = doc["rows"]
    for mname in _uniq(doc, "method"):
        for frac in (0.3, 0.5):
            seq = sorted((r["coords"]["round"], r["metrics"]["loss"])
                         for r in rows if r["coords"]["method"] == mname
                         and float(r["coords"]["main_frac"]) == frac)
            if not seq:
                continue
            hit = next((rd for rd, l in seq if l <= 1.2), -1)
            out.append((f"rounds_to_loss1.2_{int(frac * 100)}_"
                        f"{mname.replace(' ', '_')}", hit))
        l10 = [r["metrics"]["loss"] for r in rows
               if r["coords"]["method"] == mname
               and float(r["coords"]["main_frac"]) == 0.5
               and r["coords"]["round"] == 10]
        if l10:
            out.append((f"loss_at_r10_50_{mname.replace(' ', '_')}",
                        round(l10[0], 3)))
    return out


register(BenchDef(
    "fig1",
    MatrixConfig.make("fig1",
                      {"main_frac": (0.3, 0.5, 0.7),
                       "method": tuple(FIG1_METHODS)},
                      fixed=dict(model="mlp_cls", clients=10, rounds=25,
                                 h_local=6),
                      row_axes=("round",)),
    _run_fig1, _sum_fig1))


# --------------------------------------------------------------------------- #
# thm1 / thm2 — quadratic validations (experiment axis; per-case rows)
# --------------------------------------------------------------------------- #


def _run_thm1(point, ctx):
    from repro.core import theory
    from repro.data import QuadraticProblem
    if "thm1_prob" not in ctx:
        ctx["thm1_prob"] = QuadraticProblem.make(d=24, M=8, mu=0.5, L=4.0,
                                                 sigma=0.6, seed=1)
    prob = ctx["thm1_prob"]
    exp = point.coords["experiment"]
    rows = []
    if exp == "ball_vs_gamma":
        for gamma in (0.02, 0.04, 0.08):
            tail = float(np.mean([_quad_runner(prob, gamma, 4, 120,
                                               seed=s)[-10:].mean()
                                  for s in range(3)]))
            rows.append(make_row({**point.coords, "case": f"gamma{gamma}"},
                                 {"gamma": gamma, "H": 4, "M": 8,
                                  "value": tail}))
    elif exp == "ball_vs_M":
        for M in (2, 8):
            p = QuadraticProblem.make(d=24, M=M, mu=0.5, L=4.0, sigma=0.6,
                                      seed=1)
            tail = float(np.mean([_quad_runner(p, 0.06, 4, 120,
                                               seed=s)[-10:].mean()
                                  for s in range(3)]))
            rows.append(make_row({**point.coords, "case": f"M{M}"},
                                 {"gamma": 0.06, "H": 4, "M": M,
                                  "value": tail}))
    else:  # transient
        d = _quad_runner(prob, 0.05, 4, 40, seed=0)
        spec = theory.ProblemSpec(mu=0.5, L=4.0, sigma2=0.36, alpha=1,
                                  Gamma=1, M=8, H=4)
        pred = theory.thm1_rate(spec, 0.05) ** 4
        meas = (d[9] / d[0]) ** (1 / 9)
        rows.append(make_row(
            {**point.coords, "case": "rate"},
            {"transient_rate_measured": round(float(meas), 4),
             "transient_rate_bound_per_round": round(float(pred), 4)}))
    return rows


def _sum_thm1(doc):
    m = {r["coords"]["case"]: r["metrics"] for r in doc["rows"]}
    out = []
    if "gamma0.08" in m and "gamma0.02" in m:
        out.append(("ball_ratio_gamma_4x",
                    round(m["gamma0.08"]["value"] / m["gamma0.02"]["value"],
                          2)))
    if "M2" in m and "M8" in m:
        out.append(("ball_ratio_M_4x",
                    round(m["M2"]["value"] / m["M8"]["value"], 2)))
    if "rate" in m:
        out.append(("transient_rate_measured",
                    m["rate"]["transient_rate_measured"]))
        out.append(("transient_rate_bound_per_round",
                    m["rate"]["transient_rate_bound_per_round"]))
    return out


register(BenchDef(
    "thm1",
    MatrixConfig.make("thm1",
                      {"experiment": ("ball_vs_gamma", "ball_vs_M",
                                      "transient")},
                      fixed=dict(d=24, clients=8, mu=0.5, L=4.0, sigma=0.6,
                                 h_local=4),
                      row_axes=("case",)),
    _run_thm1, _sum_thm1))


def _thm2_ball(ctx, prob, H):
    balls = ctx.setdefault("thm2_balls", {})
    if H not in balls:
        balls[H] = float(np.mean([_quad_runner(prob, 0.04, H, 320 // H,
                                               seed=s)[-5:].mean()
                                  for s in range(3)]))
    return balls[H]


def _run_thm2(point, ctx):
    from repro.core import theory
    from repro.data import QuadraticProblem
    if "thm2_prob" not in ctx:
        ctx["thm2_prob"] = QuadraticProblem.make(d=24, M=8, mu=0.5, L=4.0,
                                                 sigma=0.2, heterogeneity=6.0,
                                                 seed=2)
    prob = ctx["thm2_prob"]
    if point.coords["experiment"] == "ball_vs_H":
        rows = []
        for H in (1, 4, 16):
            rows.append(make_row(
                {**point.coords, "case": f"H{H}"},
                {"gamma": 0.04, "H": H,
                 "sigma_dif2": float(prob.sigma_dif2()),
                 "value": _thm2_ball(ctx, prob, H)}))
        return rows
    # bound: crude f-gap proxy 0.5·L·dist² vs the analytic Thm-2 rhs
    spec = theory.ProblemSpec(mu=0.5, L=4.0, sigma2=0.04, alpha=1.0,
                              Gamma=1.0, M=8, H=4)
    rhs = float(theory.thm2_bound(spec, 0.04, 320 // 4,
                                  r0=float(np.sum(prob.x_star() ** 2)),
                                  sigma2_dif=prob.sigma_dif2()))
    lhs = 0.5 * 4.0 * _thm2_ball(ctx, prob, 4)
    return [make_row({**point.coords, "case": "check"},
                     {"bound_satisfied": int(lhs <= rhs),
                      "lhs": float(lhs), "rhs": rhs,
                      "bound_slack_x": round(rhs / max(lhs, 1e-12), 1)})]


def _sum_thm2(doc):
    m = {r["coords"]["case"]: r["metrics"] for r in doc["rows"]}
    out = []
    if "H16" in m and "H1" in m:
        out.append(("ball_H16_over_H1",
                    round(m["H16"]["value"] / m["H1"]["value"], 2)))
    if "check" in m:
        out.append(("bound_satisfied", m["check"]["bound_satisfied"]))
        out.append(("bound_slack_x", m["check"]["bound_slack_x"]))
    return out


register(BenchDef(
    "thm2",
    MatrixConfig.make("thm2", {"experiment": ("ball_vs_H", "bound")},
                      fixed=dict(d=24, clients=8, mu=0.5, L=4.0, sigma=0.2,
                                 heterogeneity=6.0, gamma=0.04),
                      row_axes=("case",)),
    _run_thm2, _sum_thm2))


# --------------------------------------------------------------------------- #
# sec52 — §5.2 FedAdaGrad v_{-1} critique (v_init × tau)
# --------------------------------------------------------------------------- #


def _run_sec52(point, ctx):
    from repro.core import engine
    from repro.data import QuadraticLoader, QuadraticProblem
    if "sec52_prob" not in ctx:
        ctx["sec52_prob"] = QuadraticProblem.make(d=24, M=4, mu=0.5, L=4.0,
                                                  sigma=0.3, seed=0)
    prob = ctx["sec52_prob"]
    Q = jnp.asarray(prob.Q, jnp.float32)
    b = jnp.asarray(prob.b, jnp.float32)

    def loss(params, micro):
        x = params["x"]
        return 0.5 * (x - b[0]) @ Q[0] @ (x - b[0]) + micro["z"] @ x

    f = point.fixed
    tau = point.coords["tau"]
    v_init = 1.0 if point.coords["v_init"] == "one" else None
    spec = engine.method_spec("fedadagrad", eta=0.05, eta_l=0.5 * tau,
                              tau=tau, server_beta1=0.0, v_init=v_init)
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(0),
                              lambda k: {"x": jnp.zeros(24)}, spec,
                              f["clients"])
    loader = QuadraticLoader(prob, seed=0)
    key = jax.random.PRNGKey(1)
    sn = []
    for _ in range(f["rounds"]):
        key, k = jax.random.split(key)
        state, met = step(state, jax.tree.map(
            jnp.asarray, loader.round_batch(f["h_local"])), k)
        sn.append(float(met["step_norm"]))
    return [make_row(point.coords, {"mean_step_norm": float(np.mean(sn))})]


def _sum_sec52(doc):
    m = {(r["coords"]["v_init"], float(r["coords"]["tau"])): r["metrics"]
         for r in doc["rows"]}
    out = []
    for mode, label, nd in (("one", "stall_ratio_vinit1", 1),
                            ("tau2", "stall_ratio_vinit_tau2", 2)):
        hi, lo = m.get((mode, 0.1)), m.get((mode, 1e-5))
        if hi and lo:
            out.append((label, round(hi["mean_step_norm"]
                                     / max(lo["mean_step_norm"], 1e-12), nd)))
    return out


register(BenchDef(
    "sec52",
    MatrixConfig.make("sec52",
                      {"v_init": ("one", "tau2"), "tau": (0.1, 0.001, 1e-5)},
                      fixed=dict(method="fedadagrad", rounds=5, h_local=5,
                                 clients=4)),
    _run_sec52, _sum_sec52))


# --------------------------------------------------------------------------- #
# engine — wall-time per round per method (reduced config)
# --------------------------------------------------------------------------- #


ENGINE_BENCH_METHODS = ("savic", "fedavg", "fedadagrad", "fedadam", "fedyogi",
                        "local-adam")
# shared lr settings (the async/controller arms race on the same footing);
# the adaptive-server step is ~η per coordinate, so the Adam/Yogi server
# needs a smaller η when clients are scaled too (local-adam)
ASYNC_BENCH_KW = dict(gamma=0.002, alpha=1e-2, eta_l=0.02, eta=0.1)
ASYNC_BENCH_OVERRIDES = {"local-adam": dict(eta_l=0.005, eta=0.02)}


def _run_engine(point, ctx):
    from repro.core import engine
    f, seed = point.fixed, point.seed
    data, parts = _cls_data(ctx, seed)
    method = point.coords["method"]
    init, loss, _ = _mlp(data.x.shape[1], 10)
    kw = dict(ASYNC_BENCH_KW)
    kw.update(ASYNC_BENCH_OVERRIDES.get(method, {}))
    spec = engine.method_spec(method, **kw)
    rec = _time_round_loop(spec, init, loss, data, parts, f["rounds"],
                           f["h_local"], f["clients"], seed)
    _extra(ctx, backend=jax.default_backend())
    return [make_row(point.coords, rec)]


def _sum_engine(doc):
    return [(f"round_ms_{r['coords']['method'].replace('-', '_')}",
             r["metrics"]["round_ms_mean"]) for r in doc["rows"]]


register(BenchDef(
    "engine",
    MatrixConfig.make("engine", {"method": ENGINE_BENCH_METHODS},
                      fixed=dict(model="mlp_cls_reduced", clients=8,
                                 h_local=4, rounds=12)),
    _run_engine, _sum_engine))


# --------------------------------------------------------------------------- #
# compression — bytes-on-wire × wall-time per (method, operator)
# --------------------------------------------------------------------------- #


COMPRESSION_BENCH_METHODS = ("savic", "fedavg", "fedadam")


def _run_compression(point, ctx):
    from repro.core import engine
    f, seed = point.fixed, point.seed
    data, parts = _cls_data(ctx, seed)
    method = point.coords["method"]
    op, k, ef = matrix.COMPRESSION_VARIANTS[point.coords["compression"]]
    init, loss, _ = _mlp(data.x.shape[1], 10)
    spec = engine.method_spec(
        method, **ASYNC_BENCH_KW,
        compression=engine.CompressionSpec(op=op, k=k, error_feedback=ef))
    rec = _time_round_loop(spec, init, loss, data, parts, f["rounds"],
                           f["h_local"], f["clients"], seed)
    _extra(ctx, backend=jax.default_backend())
    return [make_row(point.coords, rec,
                     info={"op": op, "k": k, "error_feedback": ef})]


def _sum_compression(doc):
    m = {(r["coords"]["method"], r["coords"]["compression"]): r["metrics"]
         for r in doc["rows"]}
    out = []
    for method in _uniq(doc, "method"):
        base, ef = m.get((method, "none")), m.get((method, "topk0.1-ef"))
        if not base or not ef:
            continue
        mname = method.replace("-", "_")
        out.append((f"wire_x_topk_{mname}",
                    round(base["wire_bytes_per_round"]
                          / ef["wire_bytes_per_round"], 1)))
        out.append((f"round_ms_topk_ef_{mname}", ef["round_ms_mean"]))
    return out


register(BenchDef(
    "compression",
    MatrixConfig.make("compression",
                      {"method": COMPRESSION_BENCH_METHODS,
                       "compression": tuple(matrix.COMPRESSION_VARIANTS)},
                      fixed=dict(model="mlp_cls_reduced", clients=8,
                                 h_local=4, rounds=10)),
    _run_compression, _sum_compression,
    note="EF topk / int8 rows double as end-to-end convergence sanity "
         "(final_loss); wire bytes are analytic (engine.bytes_on_wire)"))


# --------------------------------------------------------------------------- #
# async — sync vs buffered-async vs adaptive controller (method × arm)
# --------------------------------------------------------------------------- #


ASYNC_BENCH_BUFFER = 4       # staleness budget B for the async arm
ASYNC_BENCH_SIGMA = 0.8      # lognormal straggler sigma
# staleness-scaled server lr for buffered arms (the FedBuff discipline: a
# lagged pseudo-gradient through an adaptive normalizer needs a smaller
# server step or it oscillates divergently — measured, η=0.1 FedAdam ends
# 90× above init under B=4 lag)
ASYNC_BENCH_ASYNC_OVERRIDES = {"fedadagrad": dict(eta=0.025),
                               "fedadam": dict(eta=0.015),
                               "fedyogi": dict(eta=0.015),
                               "local-adam": dict(eta=0.005)}

# Per-method controller tuning (the static arms get per-method lr overrides;
# the controller arm gets per-method gns targets — same discipline). The GNS
# scale is method-dependent: on this task at H_t=2 the gns EMA sits around
# 3-7 for savic, ~12 for fedavg, ~8-9 for fedadagrad, ~5-8 for fedadam/yogi,
# ~6 for local-adam. noise_target sits just above the early-phase plateau so
# H_t grows only once accumulated heterogeneity noise crosses it; local-adam
# diverges under tiny partial rounds, so it starts near the full budget and
# grows immediately.
CONTROLLER_H_MIN = 2            # >= 2 active clients; at h=1 the gns ratio
                                # degenerates to M/n_act - 1 (no variance info)
CONTROLLER_TUNE = {
    "savic": dict(noise_target=8.0),
    "fedavg": dict(noise_target=12.0),
    "fedadagrad": dict(noise_target=8.5),
    "fedadam": dict(noise_target=9.0),
    "fedyogi": dict(noise_target=9.0),
    "local-adam": dict(noise_target=5.0, h_min=5),
}


def _async_env(ctx, fixed, seed):
    """Straggler trace + data shared by all three arms (and recorded in the
    document config so the race is reproducible from the artifact alone)."""
    if "async_env" in ctx:
        return ctx["async_env"]
    from repro.data.federated import (local_steps_from_times,
                                      sample_step_times, simulated_round_time)
    M, H = fixed["clients"], fixed["h_local"]
    data, parts = _cls_data(ctx, seed)
    step_times = sample_step_times("lognormal", M, seed=seed,
                                   sigma=ASYNC_BENCH_SIGMA)
    h_m = tuple(int(h) for h in local_steps_from_times(step_times, H))
    sim_t = {
        "sync": simulated_round_time(step_times, [H] * M, barrier="sync"),
        "async": simulated_round_time(step_times, h_m, barrier="async",
                                      buffer_rounds=ASYNC_BENCH_BUFFER),
    }
    ctx["async_env"] = dict(data=data, parts=parts, step_times=step_times,
                            h_m=h_m, sim_t=sim_t)
    _extra(ctx,
           het_model="lognormal", sigma=ASYNC_BENCH_SIGMA,
           step_times=[round(float(t), 4) for t in step_times],
           local_steps_async=list(h_m),
           buffer_rounds=ASYNC_BENCH_BUFFER,
           staleness_weight="polynomial",
           controller={"h_min": CONTROLLER_H_MIN, "h_max": H,
                       "buffer_max": ASYNC_BENCH_BUFFER,
                       "rounds": ASYNC_BENCH_BUFFER * fixed["rounds"],
                       "per_method_tune": CONTROLLER_TUNE},
           backend=jax.default_backend())
    return ctx["async_env"]


def _async_target(ctx, method):
    """Shared time-to-loss target: set by the sync arm of this run; partial
    (--select) runs fall back to the committed sync row."""
    t = ctx.get("targets", {}).get(method)
    if t is not None:
        return t
    path = matrix.bench_paths("async")[0]
    if os.path.exists(path):
        doc = json.load(open(path))
        for r in doc.get("rows", []):
            if r["coords"].get("method") == method \
                    and r["coords"].get("arm") == "sync":
                return r["metrics"]["target_loss"]
    raise RuntimeError(f"no sync target_loss for {method!r}: run the sync "
                       "arm first (or keep arm=sync in --select)")


def _run_async(point, ctx):
    """One (method, arm) race against the simulated straggler clock.

    sync: uniform H, server waits for the slowest client (period max t_m·H).
    async: budgeted H_m + B-round staleness buffer (period max(t_m·H_m)/B),
    4·rounds rounds so both arms spend comparable simulated time.
    controller: the adaptive arm (DESIGN.md §10) — H_t grows while the
    gradient-noise-scale EMA exceeds its per-method target; its simulated
    clock advances by the REALIZED ctrl_h_m/ctrl_b_eff knobs through the
    same systems model, so the race is apples-to-apples.
    """
    from repro.core import engine
    from repro.data import FederatedLoader
    from repro.data.federated import simulated_round_time

    f, seed = point.fixed, point.seed
    M, H = f["clients"], f["h_local"]
    env = _async_env(ctx, f, seed)
    method, arm = point.coords["method"], point.coords["arm"]
    kw = dict(ASYNC_BENCH_KW)
    kw.update(ASYNC_BENCH_OVERRIDES.get(method, {}))
    if arm in ("async", "controller"):
        kw.update(ASYNC_BENCH_ASYNC_OVERRIDES.get(method, {}))
    init, loss, _ = _mlp(env["data"].x.shape[1], 10)
    n_rounds = f["rounds"] if arm == "sync" else ASYNC_BENCH_BUFFER * f["rounds"]
    tune = None
    if arm == "sync":
        arm_kw = {}
    elif arm == "async":
        arm_kw = dict(local_steps=env["h_m"],
                      asynchrony=engine.AsyncSpec(
                          buffer_rounds=ASYNC_BENCH_BUFFER,
                          weighting="polynomial"))
    else:
        tune = dict(h_min=CONTROLLER_H_MIN)
        tune.update(CONTROLLER_TUNE.get(method, {}))
        arm_kw = dict(
            asynchrony=engine.AsyncSpec(buffer_rounds=ASYNC_BENCH_BUFFER,
                                        weighting="polynomial"),
            controller=engine.ControllerSpec(
                enabled=True, h_max=H, buffer_max=ASYNC_BENCH_BUFFER,
                step_times=tuple(float(t) for t in env["step_times"]),
                **tune))
    spec = engine.method_spec(method, **kw, **arm_kw)
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(seed), init, spec, M)
    loader = FederatedLoader(env["data"].x, env["data"].y.astype(np.int32),
                             env["parts"][:M], batch_size=32, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    times, losses, h_t_log = [], [], []
    sim_elapsed, sim_hit, r_hit = 0.0, -1.0, -1
    target = None if arm == "sync" else _async_target(ctx, method)
    for _ in range(n_rounds):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H))
        t0 = time.perf_counter()
        state, met = step(state, batch, k)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(met["loss"]))
        if arm == "controller":
            # simulated clock advances by the round shape the controller
            # actually realized this round
            h_real = [int(h) for h in np.asarray(met["ctrl_h_m"])]
            sim_elapsed += simulated_round_time(
                env["step_times"], h_real, barrier="async",
                buffer_rounds=int(met["ctrl_b_eff"]))
            h_t_log.append(int(met["ctrl_h_t"]))
            if target is None:
                target = _async_target(ctx, method)
            if r_hit < 0 and losses[-1] <= target:
                r_hit, sim_hit = len(losses), round(sim_elapsed, 4)
    if arm == "sync" and target is None:
        target = losses[0] * 0.55   # shared, reachable by both arms
        ctx.setdefault("targets", {})[method] = target
    if arm == "controller":
        # compact knob trajectory: (round, H_t) at each change point
        h_t_changes = [[r, h] for r, h in enumerate(h_t_log)
                       if r == 0 or h != h_t_log[r - 1]]
        rec = {
            "sim_time_total": round(sim_elapsed, 4),
            "round_ms_mean": round(float(np.mean(times[1:])), 3),
            "rounds": n_rounds,
            "final_loss": round(losses[-1], 4),
            "target_loss": round(target, 4),
            "rounds_to_target": r_hit,
            "sim_time_to_target": sim_hit,
            "b_eff": int(np.asarray(state["ctrl"]["b_eff"])),
            "h_t_trajectory": h_t_changes,
            "tune": tune,
        }
    else:
        r_hit = next((r + 1 for r, l in enumerate(losses) if l <= target), -1)
        rec = {
            "sim_round_time": round(env["sim_t"][arm], 4),
            "round_ms_mean": round(float(np.mean(times[1:])), 3),
            "rounds": n_rounds,
            "final_loss": round(losses[-1], 4),
            "target_loss": round(target, 4),
            "rounds_to_target": r_hit,
            "sim_time_to_target": round(r_hit * env["sim_t"][arm], 4)
            if r_hit > 0 else -1.0,
        }
    return [make_row(point.coords, rec)]


def _sum_async(doc):
    m = {(r["coords"]["method"], r["coords"]["arm"]): r["metrics"]
         for r in doc["rows"]}
    out = []
    for method in _uniq(doc, "method"):
        mname = method.replace("-", "_")
        s, a, c = (m.get((method, arm))
                   for arm in ("sync", "async", "controller"))
        if s and a and s["sim_time_to_target"] > 0 \
                and a["sim_time_to_target"] > 0:
            out.append((f"sim_speedup_{mname}",
                        round(s["sim_time_to_target"]
                              / a["sim_time_to_target"], 2)))
        if a:
            out.append((f"final_loss_async_{mname}", a["final_loss"]))
        if c:
            out.append((f"sim_time_adaptive_{mname}",
                        c["sim_time_to_target"]))
            statics = [m[(method, arm)]["sim_time_to_target"]
                       for arm in ("sync", "async")
                       if m.get((method, arm))
                       and m[(method, arm)]["sim_time_to_target"] > 0]
            if statics and c["sim_time_to_target"] > 0:
                out.append((f"sim_speedup_vs_best_static_{mname}",
                            round(min(statics)
                                  / c["sim_time_to_target"], 2)))
    return out


register(BenchDef(
    "async",
    MatrixConfig.make("async",
                      {"method": ENGINE_BENCH_METHODS,
                       "arm": ("sync", "async", "controller")},
                      fixed=dict(model="mlp_cls_reduced", clients=8,
                                 h_local=6, rounds=30)),
    _run_async, _sum_async,
    note="arm axis order matters: the sync arm sets the shared target_loss "
         "(55% of its round-0 loss) the async and controller arms race to; "
         "async/controller arms run buffer_rounds*rounds rounds (their "
         "simulated rounds are ~B x shorter). Partial --select runs without "
         "arm=sync read the committed sync row's target_loss instead."))


# --------------------------------------------------------------------------- #
# objectives — semi-supervised races on a label-scarce main-class split
# --------------------------------------------------------------------------- #


# fedavg FIRST: it is the anchor that sets the shared time-to-target loss
# the adaptive methods race to (same discipline as the async bench's sync arm)
OBJECTIVES_BENCH_METHODS = ("fedavg", "savic", "fedadagrad", "fedadam",
                            "fedyogi", "local-adam")
OBJECTIVES_BENCH_LABELED_FRAC = 0.1
# the async-bench local-adam step sizes overshoot on the semi-supervised
# loss surface (hits target in 2 rounds, then oscillates); halve them
OBJECTIVES_BENCH_OVERRIDES = {"local-adam": dict(eta_l=0.002, eta=0.01)}


def _objectives_env(ctx, fixed, seed):
    """Label-scarce environment shared by every method row: the fig1-style
    main-class split plus a stratified 10%-labeled mask (DESIGN.md §12)."""
    if "obj_env" in ctx:
        return ctx["obj_env"]
    from repro.core import objectives
    from repro.data import labeled_mask
    data, parts = _cls_data(ctx, seed)
    lab = labeled_mask(data.y, OBJECTIVES_BENCH_LABELED_FRAC, seed=seed)
    obj_spec = objectives.ObjectiveSpec(kind="consistency",
                                        unlabeled_weight=0.5,
                                        noise_sigma=0.1)
    ctx["obj_env"] = dict(data=data, parts=parts, labeled=lab,
                          obj_spec=obj_spec)
    _extra(ctx, labeled_frac=OBJECTIVES_BENCH_LABELED_FRAC,
           labeled_count=int(lab.sum()),
           objective=dict(kind=obj_spec.kind,
                          unlabeled_weight=obj_spec.unlabeled_weight,
                          noise_sigma=obj_spec.noise_sigma),
           backend=jax.default_backend())
    return ctx["obj_env"]


def _objectives_target(ctx):
    """FedAvg-anchored time-to-loss target: set by this run's fedavg row;
    partial (--select) runs fall back to the committed fedavg row."""
    t = ctx.get("obj_target")
    if t is not None:
        return t
    path = matrix.bench_paths("objectives")[0]
    if os.path.exists(path):
        doc = json.load(open(path))
        for r in doc.get("rows", []):
            if r["coords"].get("method") == "fedavg":
                return r["metrics"]["target_loss"]
    raise RuntimeError("no fedavg target_loss for the objectives bench: run "
                       "the fedavg row first (or keep method=fedavg in "
                       "--select)")


def _run_objectives(point, ctx):
    """One method racing on 10%-labeled heterogeneous clients: every client
    differentiates the consistency-regularized semi-supervised objective;
    the adaptive methods' scaling must beat FedAvg's rounds-to-target."""
    from repro.core import engine, objectives
    from repro.data import FederatedLoader

    f, seed = point.fixed, point.seed
    M, H = f["clients"], f["h_local"]
    env = _objectives_env(ctx, f, seed)
    method = point.coords["method"]
    kw = dict(ASYNC_BENCH_KW)
    kw.update(ASYNC_BENCH_OVERRIDES.get(method, {}))
    kw.update(OBJECTIVES_BENCH_OVERRIDES.get(method, {}))
    init, _, _ = _mlp(env["data"].x.shape[1], 10)

    def logits_fn(params, x):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    obj = objectives.classification_objective(env["obj_spec"], logits_fn)
    spec = engine.method_spec(method, **kw)
    step = jax.jit(engine.build_round_step(obj.base_loss, spec,
                                           objective=obj))
    state = engine.init_state(jax.random.PRNGKey(seed), init, spec, M)
    loader = FederatedLoader(env["data"].x, env["data"].y.astype(np.int32),
                             env["parts"][:M], batch_size=32, seed=seed,
                             labeled=env["labeled"])
    key = jax.random.PRNGKey(seed + 1)
    times, losses = [], []
    for _ in range(f["rounds"]):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H))
        t0 = time.perf_counter()
        state, met = step(state, batch, k)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(met["loss"]))
    if method == "fedavg":
        target = losses[0] * 0.55          # shared, reachable by every method
        ctx["obj_target"] = target
    else:
        target = _objectives_target(ctx)
    r_hit = next((r + 1 for r, l in enumerate(losses) if l <= target), -1)
    rec = {
        "round_ms_mean": round(float(np.mean(times[1:])), 3),
        "rounds": f["rounds"],
        "final_loss": round(losses[-1], 4),
        "target_loss": round(target, 4),
        "rounds_to_target": r_hit,
    }
    return [make_row(point.coords, rec)]


def _sum_objectives(doc):
    m = {r["coords"]["method"]: r["metrics"] for r in doc["rows"]}
    base = m.get("fedavg")
    out = []
    for method in _uniq(doc, "method"):
        mname = method.replace("-", "_")
        rm = m[method]
        out.append((f"final_loss_{mname}", rm["final_loss"]))
        if method != "fedavg" and base \
                and base["rounds_to_target"] > 0 and rm["rounds_to_target"] > 0:
            out.append((f"speedup_vs_fedavg_{mname}",
                        round(base["rounds_to_target"]
                              / rm["rounds_to_target"], 2)))
    return out


register(BenchDef(
    "objectives",
    MatrixConfig.make("objectives", {"method": OBJECTIVES_BENCH_METHODS},
                      fixed=dict(model="mlp_cls_reduced", clients=8,
                                 h_local=4, rounds=30)),
    _run_objectives, _sum_objectives,
    note="method axis order matters: the fedavg row sets the shared "
         "target_loss (55% of its round-0 loss) the adaptive methods race "
         "to on the 10%-labeled main-class split. Partial --select runs "
         "without method=fedavg read the committed fedavg row's target_loss "
         "instead."))


# --------------------------------------------------------------------------- #
# comm — analytic communication volume per round (arch)
# --------------------------------------------------------------------------- #


def _run_comm(point, ctx):
    from repro.configs import get_config
    arch = point.coords["arch"]
    cfg = get_config(arch)
    n = cfg.param_count()
    savic_bytes = 2 * 4 * n          # params + momentum all-reduce, fp32
    ddp_bytes = 4 * n * 8            # grad all-reduce every step, H=8
    if "dryrun_counted" not in ctx:
        ctx["dryrun_counted"] = True
        ddir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                            "results", "dryrun")
        if os.path.isdir(ddir):
            import glob
            _extra(ctx, dryrun_records_single_pod=len(
                glob.glob(os.path.join(ddir, "*__16x16.json"))))
    return [make_row(point.coords,
                     {"params": n,
                      "savic_sync_GB_per_round": savic_bytes / 1e9,
                      "ddp_GB_per_round_H8": ddp_bytes / 1e9,
                      "saving_x": ddp_bytes / savic_bytes})]


def _sum_comm(doc):
    out = [("mean_saving_x",
            round(float(np.mean([r["metrics"]["saving_x"]
                                 for r in doc["rows"]])), 1))]
    n_rec = doc["config"].get("dryrun_records_single_pod")
    if n_rec is not None:
        out.append(("dryrun_records_single_pod", n_rec))
    return out


try:
    from repro.configs import ARCH_IDS as _ARCH_IDS
except Exception:                    # repro not importable (no PYTHONPATH=src)
    _ARCH_IDS = ()
if _ARCH_IDS:
    register(BenchDef(
        "comm",
        MatrixConfig.make("comm", {"arch": tuple(_ARCH_IDS)},
                          fixed=dict(h_local=8, dtype="fp32")),
        _run_comm, _sum_comm))


# --------------------------------------------------------------------------- #
# kernels — µs/call (interpret mode: correctness-path timing, NOT TPU perf)
# --------------------------------------------------------------------------- #


KERNELS_MICRO = ("scaled_update_1M", "flash_attn_512", "ssd_256")


def _run_kernels(point, ctx):
    from repro.kernels import ops, ref
    name = point.coords["kernel"]
    k = jax.random.key(0)
    if name == "scaled_update_1M":
        n = 1 << 20
        p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (n,))
                   for i in range(3))
        d = jax.random.uniform(jax.random.fold_in(k, 3), (n,), minval=0.1,
                               maxval=2.0)
        kw = dict(gamma=0.1, beta1=0.9, alpha=1e-3)
        us_k = _time(lambda: ops.scaled_update(p, m, g, d, **kw))
        us_r = _time(jax.jit(lambda p, m, g, d: ref.scaled_update_ref(
            p, m, g, d, **kw)), p, m, g, d)
    elif name == "flash_attn_512":
        B, S, H, D = 1, 512, 4, 64
        q, kk, v = (jax.random.normal(jax.random.fold_in(k, 10 + i),
                                      (B, S, H, D)) for i in range(3))
        us_k = _time(lambda: ops.flash_attention(q, kk, v, bq=128, bk=128))
        us_r = _time(jax.jit(lambda q, kk, v: ref.attention_ref(
            q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))), q, kk, v)
    else:  # ssd_256
        B, S, H, P, N = 1, 256, 4, 32, 16
        xh = jax.random.normal(jax.random.fold_in(k, 20), (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 21),
                                               (B, S, H)))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 22), (H,)))
        Bm = jax.random.normal(jax.random.fold_in(k, 23), (B, S, H, N))
        Cm = jax.random.normal(jax.random.fold_in(k, 24), (B, S, H, N))
        us_k = _time(lambda: ops.ssd(xh, dt, A, Bm, Cm, chunk=64))
        us_r = _time(jax.jit(lambda *a: ref.ssd_ref(*a)), xh, dt, A, Bm, Cm)
    _extra(ctx, backend=jax.default_backend())
    return [make_row(point.coords, {"us_interpret": us_k, "us_ref_jit": us_r})]


def _sum_kernels(doc):
    return [(f"{r['coords']['kernel']}_us", round(r["metrics"]["us_interpret"]))
            for r in doc["rows"]]


register(BenchDef(
    "kernels",
    MatrixConfig.make("kernels", {"kernel": KERNELS_MICRO}),
    _run_kernels, _sum_kernels,
    note="interpret-mode Pallas timings vs jnp references on CPU: "
         "correctness-path timing, NOT TPU perf"))


# --------------------------------------------------------------------------- #
# serve — production decode path (arch × mode)
# --------------------------------------------------------------------------- #


SERVE_BENCH_ARCHS = ("qwen2-0.5b", "mamba2-1.3b")
SERVE_BENCH_MODES = ("reuse", "replay", "continuous", "static")
SERVE_BENCH_TRACE = dict(slots=4, n_requests=10, arrival_rate=0.6)


def _serve_arch(ctx, arch, fixed, seed):
    """All four serve modes for one arch, computed once per run (reuse and
    replay must decode the same greedy ids; continuous and static share one
    Poisson arrival trace)."""
    cache = ctx.setdefault("serve_recs", {})
    if arch in cache:
        return cache[arch]
    from repro.launch.serve import (serve, serve_continuous, serve_replay,
                                    serve_static)
    kw = dict(reduced=True, batch=fixed["batch"],
              prompt_len=fixed["prompt_len"], gen_len=fixed["gen_len"],
              seed=seed, warmup=True, verbose=False)
    tkw = dict(reduced=True, prompt_len=8, gen_len=fixed["gen_len"],
               seed=seed, warmup=True, verbose=False, **SERVE_BENCH_TRACE)
    reuse = serve(arch, **kw)
    replay = serve_replay(arch, **kw)
    assert np.array_equal(reuse.tokens, replay.tokens)   # same greedy ids
    cont = serve_continuous(arch, **tkw)
    stat = serve_static(arch, **tkw)
    rec = {}
    for mode, r in (("reuse", reuse), ("replay", replay)):
        rec[mode] = dict(r.timings)
        rec[mode]["p50_token_s"] = float(np.percentile(r.per_token_s, 50))
        rec[mode]["p99_token_s"] = float(np.percentile(r.per_token_s, 99))
    for r in (cont, stat):
        m = r.metrics
        rec[m["mode"]] = {k: v for k, v in m.items()
                          if k not in ("mode", "jit_cache_sizes")}
        rec[m["mode"]]["jit_cache_step"] = m["jit_cache_sizes"]["step"]
    cache[arch] = rec
    _extra(ctx,
           trace={**SERVE_BENCH_TRACE, "prompt_len": 8,
                  "gen_len": fixed["gen_len"],
                  "clock": "decode-step units; prefill=0 steps"},
           warmup=True, greedy=True, backend=jax.default_backend())
    return rec


def _run_serve(point, ctx):
    recs = _serve_arch(ctx, point.coords["arch"], point.fixed, point.seed)
    return [make_row(point.coords, recs[point.coords["mode"]])]


def _sum_serve(doc):
    m = {(r["coords"]["arch"], r["coords"]["mode"]): r["metrics"]
         for r in doc["rows"]}
    out = []
    for arch in _uniq(doc, "arch"):
        a = arch.replace("-", "_").replace(".", "_")
        reuse, replay = m.get((arch, "reuse")), m.get((arch, "replay"))
        cont, stat = m.get((arch, "continuous")), m.get((arch, "static"))
        if reuse and replay:
            out.append((f"ttft_speedup_reuse_{a}",
                        round(replay["ttft_s"]
                              / max(reuse["ttft_s"], 1e-9), 2)))
            out.append((f"decode_tok_per_s_{a}",
                        round(reuse["tok_per_s"], 1)))
        if cont and stat:
            out.append((f"trace_throughput_x_continuous_{a}",
                        round(cont["tok_per_step"]
                              / max(stat["tok_per_step"], 1e-9), 2)))
    return out


register(BenchDef(
    "serve",
    MatrixConfig.make("serve",
                      {"arch": SERVE_BENCH_ARCHS, "mode": SERVE_BENCH_MODES},
                      fixed=dict(reduced=True, batch=4, prompt_len=32,
                                 gen_len=16)),
    _run_serve, _sum_serve,
    note="all arms warmup=True (compile excluded); continuous vs static "
         "compare on the same Poisson trace in decode-step clock units — "
         "on CPU-reduced configs continuous pays more prefill dispatches, "
         "so its wall tok/s can trail static even when its trace "
         "throughput wins"))


# --------------------------------------------------------------------------- #
# train_lm — federated causal-LM rounds through the production driver
# --------------------------------------------------------------------------- #


# per-method step sizes for the qwen2-0.5b-reduced Markov-stream task (tuned
# for a visible loss trend in ~10 rounds on CPU; pure-SGD clients need a much
# larger γ than adam-scaled ones on a token LM)
TRAIN_LM_OVERRIDES = {
    "savic": ["--gamma", "0.05"],
    "fedavg": ["--gamma", "6.0"],
    "fedadagrad": ["--gamma", "1.0", "--server-eta", "0.5"],
    "fedadam": ["--gamma", "1.0", "--server-eta", "0.5"],
    "fedyogi": ["--gamma", "1.0", "--server-eta", "0.5"],
    "local-adam": ["--gamma", "0.05", "--server-eta", "0.05"],
}

TRAIN_LM_ARCH = "qwen2-0.5b"


def _train_lm_projection(arch):
    """Full-shape tokens/sec/device from the dry-run cost model: roofline
    bound (compute/memory/collective, benchmarks/roofline.py terms) over the
    trip-count-corrected per-device numerators of each train artifact."""
    import glob

    from benchmarks.roofline import terms
    from repro.configs import get_shape

    ddir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "results", "dryrun")
    proj = []
    for f in sorted(glob.glob(os.path.join(ddir, f"{arch}__*.json"))):
        rec = json.load(open(f))
        if rec.get("kind") != "train" or not rec.get("ok"):
            continue
        t = terms(rec)
        bound_s = max(t["compute_s"], t["memory_s"], t["collective_s"])
        s = get_shape(rec["shape"])
        tokens = s.global_batch * s.seq_len * rec.get("h_local", 8)
        proj.append({
            "shape": rec["shape"], "mesh": rec["mesh"], "mode": rec["mode"],
            "tag": rec.get("tag", ""), "n_devices": rec["n_devices"],
            "tokens_per_round": tokens,
            "round_s_roofline": round(bound_s, 6),
            "dominant_term": t["dominant"],
            # deterministic cost-model outputs — named so diff classifies
            # them as comparable, unlike the wall-derived tokens_per_s_*
            "tok_s_dev_roofline": round(
                tokens / rec["n_devices"] / bound_s, 1),
            # compute-term bound for context: the measured-HLO memory term
            # dominates this artifact by ~500×, so the roofline number above
            # is the conservative end of the projection
            "tok_s_dev_compute_bound": round(
                tokens / rec["n_devices"] / t["compute_s"], 1),
            "model_flops_utilization": round(t["roofline_frac"], 4),
        })
    return proj


def _run_train_lm(point, ctx):
    from repro.launch import train as train_mod
    f, seed = point.fixed, point.seed
    method = point.coords["method"]
    rounds, H, M = f["rounds"], f["h_local"], f["clients"]
    b, seq = f["batch"], f["seq"]
    tokens_round = M * H * b * seq
    argv = ["--arch", TRAIN_LM_ARCH, "--reduced", "--method", method,
            "--rounds", str(rounds), "--h-local", str(H),
            "--clients", str(M), "--batch", str(b), "--seq", str(seq),
            "--seed", str(seed)] + TRAIN_LM_OVERRIDES[method]
    log = train_mod.main(argv)
    losses = [l["loss"] for l in log]
    walls = [l["wall_s"] for l in log]
    steady = walls[1:] or walls           # round 0 pays the jit compile
    tps = tokens_round / float(np.mean(steady))
    half = len(losses) // 2
    n_dev = jax.device_count()
    rec = {
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "round_wall_s_mean": round(float(np.mean(steady)), 4),
        "tokens_per_s": round(tps, 1),
        "tokens_per_s_per_device": round(tps / n_dev, 1),
        "sim_time_total": log[-1]["sim_time"],
    }
    info = {
        "loss_curve": [round(l, 4) for l in losses],
        "loss_decreasing_trend": bool(
            losses[-1] < losses[0]
            and np.mean(losses[half:]) < np.mean(losses[:half])),
    }
    _extra(ctx, arch=f"{TRAIN_LM_ARCH}-reduced",
           tokens_per_round=tokens_round, n_devices=n_dev,
           backend=jax.default_backend())
    return [make_row(point.coords, rec, info=info)]


def _post_train_lm(rows, ctx):
    out = []
    for p in _train_lm_projection(TRAIN_LM_ARCH):
        out.append(make_row(
            {"method": f"projection:{p['shape']}@{p['mesh']}"},
            {k: p[k] for k in ("n_devices", "tokens_per_round",
                               "round_s_roofline", "tok_s_dev_roofline",
                               "tok_s_dev_compute_bound",
                               "model_flops_utilization")},
            info={k: p[k] for k in ("shape", "mesh", "mode", "tag",
                                    "dominant_term")}))
    return out


def _sum_train_lm(doc):
    out = []
    for r in doc["rows"]:
        method = r["coords"]["method"]
        m = r["metrics"]
        if method.startswith("projection:"):
            shape = (r.get("info") or {}).get(
                "shape", method.split(":", 1)[1].split("@")[0])
            tsd = m.get("tok_s_dev_roofline",
                        m.get("tokens_per_s_per_device"))
            if tsd is not None:
                out.append((f"tok_s_dev_proj_{shape}", tsd))
            continue
        mname = method.replace("-", "_")
        if "loss_first" in m and "loss_last" in m:
            out.append((f"loss_drop_{mname}",
                        round(m["loss_first"] - m["loss_last"], 4)))
        if "tokens_per_s_per_device" in m:
            out.append((f"tok_s_dev_{mname}", m["tokens_per_s_per_device"]))
    return out


register(BenchDef(
    "train_lm",
    MatrixConfig.make("train_lm", {"method": ENGINE_BENCH_METHODS},
                      fixed=dict(clients=4, h_local=8, batch=4, seq=64,
                                 rounds=10)),
    _run_train_lm, _sum_train_lm, post=_post_train_lm,
    note="projection rows (method='projection:<shape>@<mesh>') come from "
         "the dry-run cost model, not a run — their tok_s_dev_* metrics "
         "are deterministic roofline outputs"))


# --------------------------------------------------------------------------- #
# legacy alias CLI — the old subcommands as thin aliases over matrix configs
# --------------------------------------------------------------------------- #


ALIASES = {
    "fig1": ("fig1",),
    "thm1": ("thm1",),
    "thm2": ("thm2",),
    "sec52": ("sec52",),
    "engine": ("engine",),
    "compression": ("compression",),
    "async": ("async",),
    "controller": ("async",),     # controller rows live on the arm axis now
    "comm": ("comm",),
    "kernels": ("kernels",),
    "serve": ("serve",),
    "train_lm": ("train_lm",),
}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="Run benches by their legacy subcommand names (thin "
                    "aliases over benchmarks.matrix configs); prints the "
                    "benchmark,metric,value trajectory lines")
    ap.add_argument("--only", default="",
                    help="comma-separated legacy names (default: all)")
    args = ap.parse_args(argv)
    names = [n for n in ALIASES if not args.only or n in args.only.split(",")]
    todo = []
    for alias in names:
        for bench in ALIASES[alias]:
            if bench in todo or bench not in matrix._registry():
                continue
            todo.append(bench)
    print("benchmark,metric,value")
    for bench in todo:
        t0 = time.time()
        doc = matrix.run_bench(bench)
        for metric, value in matrix.summarize(doc):
            print(f"{bench},{metric},{value}", flush=True)
        print(f"{bench},seconds,{time.time() - t0:.1f}", flush=True)


if __name__ == "__main__":
    main()
