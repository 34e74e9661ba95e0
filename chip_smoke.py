"""Smoke test of the main paths on a TPU: federated training and serving of
qwen2-0.5b at its published widths, with random weights made from a seed.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # the 2x2 mesh launch and its reference

One chip:
  (a) tree path, the training CLI's default: ``repro.launch.train.main``,
      savic with Adam scaling, M=2 clients, H=2, b=1, S=512, 3 rounds over
      one round of data, cut to TREE_LAYERS of 24 layers;
  (b) the fused local-step kernel's local-scaling (Adam debias) mode
      against its jnp oracle, at one qwen2-0.5b MLP matrix;
  (c) decode path: ``repro.launch.serve.serve`` at all 24 layers with the
      Pallas decode kernels, whose greedy tokens must equal the plain path's.

Every training run must give finite losses, and a round-3 loss below round
1's.

``--chips 4`` runs only the mesh launch (``--mesh debug --mesh-shape 2x2
--mode paper``: 2 clients, each model-sharded over 2 chips) at MESH_LAYERS,
and compares its losses with ``--mesh none`` on device 0; their per-round
losses must agree within LOSS_ATOL and their client drifts within
DRIFT_RTOL.

Lines starting ``[chip]`` are readings taken on the chip. Any failed check
exits non-zero; nothing is caught. The last line of stdout is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-0.5b"
FULL_LAYERS = 24
# Depth cut for one v5e (15.75 GB of HBM usable), from compiling the round
# step for a described v5e: 16 layers peak at 13.2 GB, leaving 2.5 GB.
TREE_LAYERS = 16
# The mesh launch's depth: its single-device reference runs on one chip
MESH_LAYERS = 6
# Same-seed runs of two paths, mesh vs single device. Their programs differ,
# so the chip's fp32 rounding differs, by far less than these limits. The
# client drift, driven by the updates, is compared too.
LOSS_ATOL = 1e-4        # nats
DRIFT_RTOL = 1e-2
# Every round trains on the same data round: on fresh synthetic data the
# loss of a random-weight model sits at ln(151936) and moves with the batch,
# not with training, over 3 rounds; on data the clients revisit it must fall.
TRAIN = ["--arch", ARCH, "--method", "savic", "--preconditioner", "adam",
         "--clients", "2", "--h-local", "2", "--batch", "1", "--seq", "512",
         "--rounds", "3", "--data-rounds", "1", "--seed", "0"]


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def train_phase(name, layers, extra=()):
    import jax
    from repro.launch import train
    gc.collect()               # the previous run's state is freed on device
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"[smoke] {name}: {live / 1e9:.3f} GB of arrays live at the start",
          flush=True)
    log = train.main(TRAIN + ["--layers", str(layers), *extra])
    s = log.setup
    losses = [r["loss"] for r in log]
    print(f"[chip] {name}: {layers} layers, round step compiled in "
          f"{s['compile_s']:.1f} s, compiled peak {s['peak_bytes'] / 1e9:.3f} "
          f"GB, Pallas calls {s['pallas_calls']}, peak HBM in use so far "
          f"{_gb(s['peak_bytes_in_use'])}", flush=True)
    for r in log:
        print(f"[chip] {name}: round {r['round']} loss {r['loss']:.6f} "
              f"{r['wall_s']:.4f} s {r['tokens_per_s']:.1f} tok/s",
              flush=True)
    drifts = [r["drift"] for r in log]
    print(f"[smoke] {name}: round 3 - round 1 loss {losses[-1] - losses[0]:+.3e}"
          f" nats; client drift {drifts}", flush=True)
    check(all(math.isfinite(x) for x in losses + drifts),
          f"{name}: losses {losses}, drifts {drifts}")
    check(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    check(all(x > 0 for x in drifts), f"{name}: clients did not move")
    return log


def _gb(peaks):
    return ", ".join("n/a" if b is None else f"{b / 1e9:.3f} GB"
                     for b in peaks)


def agree(name, log, ref_log):
    check(len(log) == len(ref_log), f"{name}: round counts differ")
    dl = max(abs(a["loss"] - b["loss"]) for a, b in zip(log, ref_log))
    dd = max(abs(a["drift"] - b["drift"]) / b["drift"]
             for a, b in zip(log, ref_log))
    print(f"[chip] {name}: max |loss difference| {dl:.3e} nats (limit "
          f"{LOSS_ATOL}), max relative drift difference {dd:.3e} (limit "
          f"{DRIFT_RTOL})", flush=True)
    check(dl <= LOSS_ATOL and dd <= DRIFT_RTOL,
          f"{name}: {[(r['loss'], r['drift']) for r in log]} vs "
          f"{[(r['loss'], r['drift']) for r in ref_log]}")


def fused_kernel_phase():
    """Local Adam scaling (per-client D, debias β_t) through the kernel's
    public entry point vs its jnp oracle, at one qwen2-0.5b MLP matrix plus
    a ragged tail."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    M, n = 2, 896 * 4864 + 7
    k = jax.random.key(0)
    p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (M, n))
               for i in range(3))
    d = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3), (M, n)))
    t = jnp.array([0, 5], jnp.int32)
    kw = dict(gamma=3e-3, beta1=0.9, alpha=1e-2, beta2=0.999, kind="adam",
              schedule="debias", update_d=True)
    got = ops.fused_local_step(p, m, g, d, None, t, None, **kw)
    want = jax.jit(lambda *a: ref.fused_step_ref(*a, **kw))(
        p, m, g, d, None, t, None)
    for name, a, b in zip(("p", "m", "d"), got, want):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        print(f"[chip] fused kernel, local debias: max |error| of {name}' "
              f"over max |{name}'|: {err:.3e}", flush=True)
        check(err <= 1e-5, f"fused kernel {name}' error {err}")


def decode_phase():
    import jax
    import numpy as np
    from repro.launch.serve import serve
    kw = dict(reduced=False, batch=4, prompt_len=128, gen_len=16,
              warmup=True, verbose=False)
    # both paths in fp32 arithmetic: the kernels contract on the VPU in
    # fp32, so the plain path's matmuls must not round to bf16 either
    with jax.default_matmul_precision("highest"):
        res = {k: serve(ARCH, use_decode_kernel=k, **kw)
               for k in (True, False)}
    for k, r in res.items():
        t = r.timings
        print(f"[chip] decode ({'Pallas kernels' if k else 'plain jnp'}, "
              f"{FULL_LAYERS} layers, batch 4, prompt 128): prefill "
              f"{t['prefill_s']:.4f} s, {t['tok_per_s']:.1f} tok/s over "
              f"{len(r.per_token_s)} decode steps, median step "
              f"{float(np.median(r.per_token_s)):.5f} s", flush=True)
    same = np.array_equal(res[True].tokens, res[False].tokens)
    print(f"[chip] decode: kernel tokens equal plain tokens: {same} "
          f"({res[True].tokens.size} tokens)", flush=True)
    check(same, f"decode tokens differ:\n{res[True].tokens}\n"
          f"{res[False].tokens}")


def one_chip():
    print(f"[smoke] {ARCH} training at published widths, depth cut to "
          f"{TREE_LAYERS} of {FULL_LAYERS} layers; serving at all "
          f"{FULL_LAYERS}", flush=True)
    train_phase("(a) tree", TREE_LAYERS)
    fused_kernel_phase()
    decode_phase()


def four_chips():
    import jax
    mesh = ["--mesh", "debug", "--mesh-shape", "2x2", "--mode", "paper"]
    print(f"[smoke] {ARCH} on the 2x2 mesh, paper plan (2 clients, each "
          f"model-sharded over 2 chips), depth cut to {MESH_LAYERS} of "
          f"{FULL_LAYERS} layers", flush=True)
    ref = train_phase("single device, tree", MESH_LAYERS)
    tree = train_phase("2x2 mesh, tree", MESH_LAYERS, mesh)
    agree("2x2 mesh tree vs single device", tree, ref)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"[chip] peak HBM in use per device: {_gb(peaks)}", flush=True)
    check(all(b > 0 for b in peaks), f"a device held nothing: {peaks}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    check(jax.device_count() >= args.chips,
          f"--chips {args.chips} but {jax.device_count()} devices")
    from repro.utils.compile_cache import enable_compile_cache
    print(f"[smoke] compile cache {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
