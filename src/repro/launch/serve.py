"""Batched serving driver: prefill once, reuse the cache, decode (DESIGN.md §8).

On TPU this serves the assigned configs on the production mesh (see
launch/steps.build_serve_step / build_prefill_step for the sharded serve
path); on CPU it runs reduced configs end-to-end, which is what the serving
example, benchmarks and tests use.

Four entry points:

* ``serve`` — the production path: ``model.prefill_cache`` returns the decode
  cache already populated at pos = prompt_len, so decode starts immediately
  (TTFT = one batched prefill). The cache conversion is fused into the
  prefill program, so ``cache_setup_s`` is 0 here by construction.
* ``serve_replay`` — the old per-token prompt-replay path, kept ONLY as a
  differential baseline (tests pin reuse == replay greedy tokens; the
  benchmark shows reuse dominating replay on TTFT). Timing is attributed
  honestly: the replay loop is ``cache_setup_s``, not prefill.
* ``serve_continuous`` — continuous batching over a fixed ring of ``slots``
  decode slots: requests from a synthetic Poisson arrival trace are admitted
  into free slots (single-request prefill + ``dynamic_update_slice`` into the
  slot-major cache at a *traced* slot index) and evicted on completion, while
  ONE jitted decode step with per-slot (B,) positions serves the whole ring —
  zero recompilation across request churn (asserted via jit cache size).
* ``serve_static`` — static batching baseline on the SAME trace: groups of
  ``slots`` requests, a group starts only when every member has arrived and
  the previous group drained, and runs to the longest member's length.

Scheduling comparison is in decode-step clock units (1 step = one batched
decode; prefill = 0 steps; idle waiting advances the clock), which isolates
the batching policy from CPU-vs-TPU step cost; wall-clock compute seconds are
reported alongside, honestly.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import ModelCallConfig, build, sample_batch
from repro.utils.compile_cache import enable_compile_cache


# --------------------------------------------------------------------------- #
# shared plumbing
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray      # (B, gen_len) generated ids (first from prefill)
    timings: dict           # prefill_s / cache_setup_s / decode_s / ttft_s / tok_per_s
    per_token_s: np.ndarray  # decode-loop wall seconds per step


@dataclasses.dataclass
class TraceResult:
    tokens: dict            # rid -> (gen_len_r,) np.int32
    requests: dict          # rid -> {arrival, start, finish} in step-clock units
    metrics: dict           # makespan_steps, tok_per_step, wall tok/s, p50/p99, ...


def _build(arch, *, reduced, dtype, decode_window, use_decode_kernel,
           exact_moe):
    cfg = get_config(arch, reduced=reduced)
    call = ModelCallConfig(dtype=dtype, decode_window=decode_window,
                           use_decode_kernel=use_decode_kernel,
                           exact_moe=exact_moe)
    return cfg, build(cfg, call)


def _noise(key, shape, greedy):
    """Additive sampling noise: zeros = greedy; Gumbel = categorical."""
    if greedy:
        return jnp.zeros(shape, jnp.float32), key
    key, k = jax.random.split(key)
    return jax.random.gumbel(k, shape, jnp.float32), key


def _first_token(logits, noise, vocab_size):
    lg = logits.astype(jnp.float32) + noise
    V = lg.shape[-1]
    if V > vocab_size:
        lg = jnp.where(jnp.arange(V) >= vocab_size, -jnp.inf, lg)
    return jnp.argmax(lg, -1).astype(jnp.int32)


def _jit_cache_size(fn):
    try:
        return fn._cache_size()
    except AttributeError:       # older jax
        return -1


def poisson_trace(n_requests, arrival_rate, seed, gen_len):
    """Synthetic Poisson arrival trace in decode-step clock units.

    Returns (arrivals, gens): arrival step of each request (cumulative
    exponential inter-arrival times at ``arrival_rate`` requests/step) and its
    generation length, drawn in [max(1, gen_len//2), gen_len].
    """
    rng = np.random.default_rng(seed)
    inter = rng.exponential(1.0 / arrival_rate, size=n_requests)
    arrivals = np.floor(np.cumsum(inter)).astype(np.int64)
    gens = rng.integers(max(1, gen_len // 2), gen_len + 1, size=n_requests)
    return arrivals, gens


def request_prompt(cfg, seed, rid, prompt_len):
    """Per-request B=1 prompt, deterministic in (seed, rid)."""
    return sample_batch(cfg, jax.random.fold_in(jax.random.PRNGKey(seed + 1),
                                                rid), 1, prompt_len)


# --------------------------------------------------------------------------- #
# single-batch serving: cache reuse (production) vs prompt replay (baseline)
# --------------------------------------------------------------------------- #


def serve(arch: str, *, reduced=True, batch=4, prompt_len=32, gen_len=32,
          decode_window=0, dtype=jnp.float32, greedy=True, seed=0,
          use_decode_kernel=False, exact_moe=False, cache_len=None,
          prompt=None, warmup=False, verbose=True) -> ServeResult:
    """Prefill once, decode from the returned cache — no prompt replay.

    ``warmup=True`` compiles the prefill and decode programs on a throwaway
    pass before timing, so the reported phases are steady-state (benchmarks);
    the default includes compile, matching a cold server start.
    """
    cfg, model = _build(arch, reduced=reduced, dtype=dtype,
                        decode_window=decode_window,
                        use_decode_kernel=use_decode_kernel,
                        exact_moe=exact_moe)
    params = model.init(jax.random.PRNGKey(seed))
    if prompt is None:
        prompt = sample_batch(cfg, jax.random.PRNGKey(seed + 1), batch,
                              prompt_len)
    cache_len = cache_len or (prompt_len + gen_len)

    prefill = jax.jit(model.prefill_cache, static_argnums=2)
    step = jax.jit(model.decode_sample)
    if warmup:
        lg, cw = prefill(params, prompt, cache_len)
        tw, cw = step(params, cw, jnp.zeros((batch,), jnp.int32),
                      jnp.int32(prompt_len),
                      jnp.zeros(lg.shape, jnp.float32))
        jax.block_until_ready(tw)

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompt, cache_len)
    jax.block_until_ready((logits, cache))
    t_prefill = time.perf_counter() - t0

    key = jax.random.PRNGKey(seed + 2)
    noise, key = _noise(key, logits.shape, greedy)
    tok = _first_token(logits, noise, cfg.vocab_size)

    out, per_tok = [np.asarray(tok)], []
    pos = prompt_len
    for _ in range(gen_len - 1):
        noise, key = _noise(key, logits.shape, greedy)
        ts = time.perf_counter()
        tok, cache = step(params, cache, tok, jnp.int32(pos), noise)
        tok.block_until_ready()
        per_tok.append(time.perf_counter() - ts)
        pos += 1
        out.append(np.asarray(tok))
    t_decode = float(sum(per_tok))

    timings = {"prefill_s": t_prefill, "cache_setup_s": 0.0,
               "decode_s": t_decode, "ttft_s": t_prefill,
               "tok_per_s": batch * max(gen_len - 1, 1) / max(t_decode, 1e-9)}
    if verbose:
        print(f"[serve] {arch}: prefill {t_prefill:.3f}s (TTFT), "
              f"decode {gen_len - 1} steps x{batch} = "
              f"{timings['tok_per_s']:.1f} tok/s")
    return ServeResult(np.stack(out, axis=1), timings,
                       np.asarray(per_tok, np.float64))


def serve_replay(arch: str, *, reduced=True, batch=4, prompt_len=32,
                 gen_len=32, decode_window=0, dtype=jnp.float32, greedy=True,
                 seed=0, exact_moe=False, cache_len=None, prompt=None,
                 warmup=False, verbose=True) -> ServeResult:
    """Differential baseline: build the decode cache by replaying the prompt
    token-by-token through ``model.decode``. Token-id families only (the
    replay feeds ids, not embeddings). The replay loop is reported as
    ``cache_setup_s`` — the misattribution the old driver had (it called it
    prefill) is fixed here."""
    cfg, model = _build(arch, reduced=reduced, dtype=dtype,
                        decode_window=decode_window, use_decode_kernel=False,
                        exact_moe=exact_moe)
    params = model.init(jax.random.PRNGKey(seed))
    if prompt is None:
        prompt = sample_batch(cfg, jax.random.PRNGKey(seed + 1), batch,
                              prompt_len)
    cache_len = cache_len or (prompt_len + gen_len)
    toks = prompt.get("tokens")
    if toks is None:
        toks = jnp.zeros((batch, prompt_len), jnp.int32)

    decode = jax.jit(model.decode)
    step = jax.jit(model.decode_sample)
    if warmup:
        cw = model.init_cache(batch, cache_len)
        lw, cw = decode(params, cw, toks[:, 0], jnp.int32(0))
        tw, cw = step(params, cw, toks[:, 0], jnp.int32(1),
                      jnp.zeros(lw.shape, jnp.float32))
        jax.block_until_ready(tw)

    t0 = time.perf_counter()
    cache = model.init_cache(batch, cache_len)
    logits = None
    for t in range(prompt_len):
        logits, cache = decode(params, cache, toks[:, t], jnp.int32(t))
    jax.block_until_ready(logits)
    t_setup = time.perf_counter() - t0

    key = jax.random.PRNGKey(seed + 2)
    noise, key = _noise(key, logits.shape, greedy)
    tok = _first_token(logits, noise, cfg.vocab_size)

    out, per_tok = [np.asarray(tok)], []
    pos = prompt_len
    for _ in range(gen_len - 1):
        noise, key = _noise(key, logits.shape, greedy)
        ts = time.perf_counter()
        tok, cache = step(params, cache, tok, jnp.int32(pos), noise)
        tok.block_until_ready()
        per_tok.append(time.perf_counter() - ts)
        pos += 1
        out.append(np.asarray(tok))
    t_decode = float(sum(per_tok))

    timings = {"prefill_s": 0.0, "cache_setup_s": t_setup,
               "decode_s": t_decode, "ttft_s": t_setup,
               "tok_per_s": batch * max(gen_len - 1, 1) / max(t_decode, 1e-9)}
    if verbose:
        print(f"[serve-replay] {arch}: replay {t_setup:.3f}s (TTFT), "
              f"decode {gen_len - 1} steps x{batch} = "
              f"{timings['tok_per_s']:.1f} tok/s")
    return ServeResult(np.stack(out, axis=1), timings,
                       np.asarray(per_tok, np.float64))


# --------------------------------------------------------------------------- #
# continuous vs static batching over a Poisson arrival trace
# --------------------------------------------------------------------------- #


def serve_continuous(arch: str, *, reduced=True, slots=4, n_requests=8,
                     prompt_len=8, gen_len=8, arrival_rate=0.5,
                     decode_window=0, dtype=jnp.float32, greedy=True, seed=0,
                     use_decode_kernel=False, exact_moe=False, warmup=False,
                     verbose=True) -> TraceResult:
    """Continuous batching: per-slot admission/eviction on a fixed decode ring.

    One jitted decode step (per-slot (B,) positions) serves every composition
    of in-flight requests; admission is a single-request prefill inserted into
    the slot-major cache at a traced slot index. Nothing recompiles as
    requests churn — asserted on the jit cache sizes at the end.
    """
    cfg, model = _build(arch, reduced=reduced, dtype=dtype,
                        decode_window=decode_window,
                        use_decode_kernel=use_decode_kernel,
                        exact_moe=exact_moe)
    params = model.init(jax.random.PRNGKey(seed))
    cache_len = prompt_len + gen_len
    arrivals, gens = poisson_trace(n_requests, arrival_rate, seed, gen_len)
    prompts = [request_prompt(cfg, seed, r, prompt_len)
               for r in range(n_requests)]

    prefill = jax.jit(model.prefill_cache, static_argnums=2)
    step = jax.jit(model.decode_sample)

    @jax.jit
    def insert_slot(cache, one, b):
        # every decode-cache leaf is slot-major with batch at dim 1
        return jax.tree.map(
            lambda f, o: jax.lax.dynamic_update_slice_in_dim(f, o, b, axis=1),
            cache, one)

    cache = model.init_cache(slots, cache_len)
    if warmup:
        lw, cw = prefill(params, prompts[0], cache_len)
        c2 = insert_slot(cache, cw, jnp.int32(0))
        tw, c2 = step(params, c2, jnp.zeros((slots,), jnp.int32),
                      jnp.zeros((slots,), jnp.int32),
                      jnp.zeros((slots, lw.shape[-1]), jnp.float32))
        jax.block_until_ready(tw)
        cache = model.init_cache(slots, cache_len)
    V = None
    toks = np.zeros((slots,), np.int32)
    pos = np.zeros((slots,), np.int32)
    active = np.zeros((slots,), bool)
    rid_of = np.full((slots,), -1)
    remaining = np.zeros((slots,), np.int64)
    out_tokens = {r: [] for r in range(n_requests)}
    requests = {r: {"arrival": int(arrivals[r]), "start": None,
                    "finish": None} for r in range(n_requests)}
    key = jax.random.PRNGKey(seed + 2)
    next_req, n_done, clock = 0, 0, 0
    per_step_s, t_prefill_total = [], 0.0
    t_run0 = time.perf_counter()

    while n_done < n_requests:
        # --- admission: fill free slots with arrived requests -------------- #
        for b in range(slots):
            if active[b] or next_req >= n_requests \
                    or arrivals[next_req] > clock:
                continue
            r = next_req
            next_req += 1
            tp = time.perf_counter()
            logits1, c1 = prefill(params, prompts[r], cache_len)
            cache = insert_slot(cache, c1, jnp.int32(b))
            jax.block_until_ready(logits1)
            t_prefill_total += time.perf_counter() - tp
            V = logits1.shape[-1]
            noise, key = _noise(key, (1, V), greedy)
            t0 = int(np.asarray(_first_token(logits1, noise,
                                             cfg.vocab_size))[0])
            out_tokens[r].append(t0)
            requests[r]["start"] = clock
            if gens[r] == 1:                      # done at admission
                requests[r]["finish"] = clock
                n_done += 1
                continue
            toks[b], pos[b] = t0, prompt_len
            active[b], rid_of[b], remaining[b] = True, r, gens[r] - 1

        if not active.any():
            # ring empty: jump the clock to the next arrival
            clock = max(clock + 1, int(arrivals[next_req]))
            continue

        # --- one batched decode step over the whole ring ------------------- #
        noise, key = _noise(key, (slots, V), greedy)
        ts = time.perf_counter()
        tok_dev, cache = step(params, cache, jnp.asarray(toks),
                              jnp.asarray(pos), noise)
        tok_dev.block_until_ready()
        per_step_s.append(time.perf_counter() - ts)
        new_toks = np.asarray(tok_dev)
        clock += 1
        for b in range(slots):
            if not active[b]:
                continue
            r = rid_of[b]
            out_tokens[r].append(int(new_toks[b]))
            toks[b] = new_toks[b]
            pos[b] += 1
            remaining[b] -= 1
            if remaining[b] == 0:                 # eviction: free the slot
                requests[r]["finish"] = clock
                active[b], rid_of[b] = False, -1
                n_done += 1

    t_wall = time.perf_counter() - t_run0
    total = int(sum(gens))
    makespan = max(rq["finish"] for rq in requests.values())
    delays = [rq["start"] - rq["arrival"] for rq in requests.values()]
    per = np.asarray(per_step_s, np.float64)
    metrics = {
        "mode": "continuous", "slots": slots, "n_requests": n_requests,
        "total_tokens": total, "makespan_steps": int(makespan),
        "tok_per_step": total / max(makespan, 1),
        "decode_steps": len(per_step_s),
        "wall_s": t_wall, "prefill_s": t_prefill_total,
        "decode_s": float(per.sum()),
        "wall_tok_per_s": total / max(t_wall, 1e-9),
        "p50_step_s": float(np.percentile(per, 50)) if len(per) else 0.0,
        "p99_step_s": float(np.percentile(per, 99)) if len(per) else 0.0,
        "mean_queue_delay_steps": float(np.mean(delays)),
        "max_queue_delay_steps": int(np.max(delays)),
        "jit_cache_sizes": {"step": _jit_cache_size(step),
                            "prefill": _jit_cache_size(prefill),
                            "insert": _jit_cache_size(insert_slot)},
    }
    if verbose:
        print(f"[serve-continuous] {arch}: {n_requests} reqs / {slots} slots: "
              f"{total} tok in {makespan} steps "
              f"({metrics['tok_per_step']:.2f} tok/step, "
              f"{metrics['wall_tok_per_s']:.1f} tok/s wall)")
    return TraceResult({r: np.asarray(t, np.int32)
                        for r, t in out_tokens.items()}, requests, metrics)


def serve_static(arch: str, *, reduced=True, slots=4, n_requests=8,
                 prompt_len=8, gen_len=8, arrival_rate=0.5, decode_window=0,
                 dtype=jnp.float32, greedy=True, seed=0,
                 use_decode_kernel=False, exact_moe=False, warmup=False,
                 verbose=True) -> TraceResult:
    """Static-batching baseline on the SAME Poisson trace as serve_continuous:
    requests are served in arrival-order groups of ``slots``; a group starts
    only when all members have arrived and the previous group has drained, and
    decodes to the longest member's length (short members pad)."""
    cfg, model = _build(arch, reduced=reduced, dtype=dtype,
                        decode_window=decode_window,
                        use_decode_kernel=use_decode_kernel,
                        exact_moe=exact_moe)
    params = model.init(jax.random.PRNGKey(seed))
    cache_len = prompt_len + gen_len
    arrivals, gens = poisson_trace(n_requests, arrival_rate, seed, gen_len)
    prompts = [request_prompt(cfg, seed, r, prompt_len)
               for r in range(n_requests)]

    prefill = jax.jit(model.prefill_cache, static_argnums=2)
    step = jax.jit(model.decode_sample)
    if warmup:
        bw = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                          *([prompts[0]] * slots))
        lw, cw = prefill(params, bw, cache_len)
        tw, cw = step(params, cw, jnp.zeros((slots,), jnp.int32),
                      jnp.zeros((slots,), jnp.int32),
                      jnp.zeros((slots, lw.shape[-1]), jnp.float32))
        jax.block_until_ready(tw)

    out_tokens = {r: [] for r in range(n_requests)}
    requests = {r: {"arrival": int(arrivals[r]), "start": None,
                    "finish": None} for r in range(n_requests)}
    key = jax.random.PRNGKey(seed + 2)
    clock = 0
    per_step_s, t_prefill_total = [], 0.0
    t_run0 = time.perf_counter()

    for g0 in range(0, n_requests, slots):
        grp = list(range(g0, min(g0 + slots, n_requests)))
        # pad the last group by repeating its final member (outputs ignored)
        padded = grp + [grp[-1]] * (slots - len(grp))
        start = max(clock, max(int(arrivals[r]) for r in grp))
        batch = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *[prompts[r] for r in padded])
        tp = time.perf_counter()
        logits, cache = prefill(params, batch, cache_len)
        jax.block_until_ready(logits)
        t_prefill_total += time.perf_counter() - tp
        V = logits.shape[-1]
        noise, key = _noise(key, (slots, V), greedy)
        toks = _first_token(logits, noise, cfg.vocab_size)
        first = np.asarray(toks)
        for i, r in enumerate(grp):
            out_tokens[r].append(int(first[i]))
            requests[r]["start"] = start
            requests[r]["finish"] = start + int(gens[r]) - 1
        mg = max(int(gens[r]) for r in grp)
        for t in range(mg - 1):
            noise, key = _noise(key, (slots, V), greedy)
            posv = np.full((slots,), prompt_len + t, np.int32)
            ts = time.perf_counter()
            toks, cache = step(params, cache, toks, jnp.asarray(posv), noise)
            toks.block_until_ready()
            per_step_s.append(time.perf_counter() - ts)
            new = np.asarray(toks)
            for i, r in enumerate(grp):
                if t + 1 < int(gens[r]):
                    out_tokens[r].append(int(new[i]))
        clock = start + mg - 1

    t_wall = time.perf_counter() - t_run0
    total = int(sum(gens))
    makespan = max(rq["finish"] for rq in requests.values())
    delays = [rq["start"] - rq["arrival"] for rq in requests.values()]
    per = np.asarray(per_step_s, np.float64)
    metrics = {
        "mode": "static", "slots": slots, "n_requests": n_requests,
        "total_tokens": total, "makespan_steps": int(makespan),
        "tok_per_step": total / max(makespan, 1),
        "decode_steps": len(per_step_s),
        "wall_s": t_wall, "prefill_s": t_prefill_total,
        "decode_s": float(per.sum()),
        "wall_tok_per_s": total / max(t_wall, 1e-9),
        "p50_step_s": float(np.percentile(per, 50)) if len(per) else 0.0,
        "p99_step_s": float(np.percentile(per, 99)) if len(per) else 0.0,
        "mean_queue_delay_steps": float(np.mean(delays)),
        "max_queue_delay_steps": int(np.max(delays)),
        "jit_cache_sizes": {"step": _jit_cache_size(step),
                            "prefill": _jit_cache_size(prefill)},
    }
    if verbose:
        print(f"[serve-static] {arch}: {n_requests} reqs / {slots} slots: "
              f"{total} tok in {makespan} steps "
              f"({metrics['tok_per_step']:.2f} tok/step, "
              f"{metrics['wall_tok_per_s']:.1f} tok/s wall)")
    return TraceResult({r: np.asarray(t, np.int32)
                        for r, t in out_tokens.items()}, requests, metrics)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--mode", default="reuse",
                    choices=["reuse", "replay", "continuous", "static"])
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size (reuse/replay) or decode slots (traces)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--decode-window", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-greedy", action="store_true")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="fused Pallas decode attention + sampling")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="Poisson arrivals per decode step (trace modes)")
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device {dev.platform} {dev.device_kind} "
          f"x{jax.device_count()}", flush=True)
    common = dict(reduced=not args.full, prompt_len=args.prompt_len,
                  gen_len=args.gen_len, decode_window=args.decode_window,
                  seed=args.seed, greedy=not args.no_greedy)
    if args.mode == "reuse":
        serve(args.arch, batch=args.batch,
              use_decode_kernel=args.decode_kernel, **common)
    elif args.mode == "replay":
        serve_replay(args.arch, batch=args.batch, **common)
    else:
        fn = serve_continuous if args.mode == "continuous" else serve_static
        fn(args.arch, slots=args.batch, n_requests=args.requests,
           arrival_rate=args.arrival_rate,
           use_decode_kernel=args.decode_kernel, **common)


if __name__ == "__main__":
    main()
