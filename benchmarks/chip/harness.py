"""One run of one cell: set-up, the measured window, a traced tail when
asked, and the check against the plain reference.

Set-up builds one object, the compiled round step and its state, and drives
it through ``CHECK_ROUNDS`` rounds by the window's own feed and call; those
rounds are the warm-up and the program's side of the check. The window then
calls the same step round after round, as ``launch/train.py``'s loop does:
make the round's batch on the host, put it on the devices, call the step on
the donated state, read the loss back. It counts whole rounds only. After
the window (and the traced tail) the state is freed and the reference runs
the first rounds again, from the same seeded weights and batches.
"""
from __future__ import annotations

import functools
import gc
import math
import shutil
import sys
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, program, spec, trace
from benchmarks.chip.reference import common
from benchmarks.chip.reference.savic import SavicReference
from benchmarks.chip.traffic.generator import RoundTraffic

CHECK_ROUNDS = 3
SAMPLE = 1 << 18            # elements of a leaf compared one by one
TRACE_SECONDS = 2.0         # rounds traced after the window: at least this
TRACE_ROUNDS = (3, 40)      # ... and within these bounds


def log(*parts):
    print("[bench]", *parts, file=sys.stderr, flush=True)


def _round(prog, state, traffic, r):
    """One round through the window's feed and call: (state, loss, seconds
    from dispatch to the loss read back)."""
    with jax.profiler.TraceAnnotation("make_batch"):
        host = traffic.round(r)
    with jax.profiler.TraceAnnotation("put_batch"):
        batch = prog.put(host)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("dispatch"):
        state, metrics = prog.step(state, batch, r)
    with jax.profiler.TraceAnnotation("read_loss"):
        loss = float(metrics["loss"])
    return state, loss, time.perf_counter() - t


def _reference(conf):
    return spec.reference(conf["model_type"])


def _shapes(conf):
    """The reference's parameter tree, as shapes."""
    return common.shapes_from_table(_reference(conf).param_table(conf))


def weights(conf):
    """The seeded weights of one replica, ``make(key)``, checked against the
    program's own parameter tree leaf for leaf."""
    ours = _shapes(conf)
    theirs = program.param_shapes(program.model_config(conf))
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(ours), jax.tree.leaves(theirs))):
        raise ValueError(f"the benchmark's {conf['model_type']} weights do "
                         f"not match the program's parameter tree:\n"
                         f"{ours}\n{theirs}")
    return functools.partial(_reference(conf).init_params, cfg=conf)


def leaf_names(conf) -> list:
    """'/'-joined paths of the parameter leaves, in the order the readings
    list them."""
    return ["/".join(str(k.key) for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(_shapes(conf))[0]]


def sample_index(conf, seed) -> list:
    """Per leaf, the flat indices of the elements that ``grad_err`` and
    ``change_err`` compare: all of a leaf of at most ``SAMPLE``, else
    ``SAMPLE`` drawn from the seed."""
    key = jax.random.fold_in(common.seed_key(seed), 1)
    return [jnp.arange(n, dtype=jnp.int32) if n <= SAMPLE else
            jax.random.randint(jax.random.fold_in(key, i), (SAMPLE,), 0, n,
                               dtype=jnp.int32)
            for i, n in enumerate(leaf_sizes(conf))]


def leaf_sizes(conf) -> list:
    """Elements of each parameter leaf, in tree-flattening order."""
    return [math.prod(leaf.shape)
            for leaf in jax.tree.leaves(_shapes(conf))]


def read_leaves(tree, index):
    """(each leaf's norm, its elements at ``index``), in float32; jit-able."""
    leaves = [a.astype(jnp.float32) for a in jax.tree.leaves(tree)]
    return ([jnp.sqrt(jnp.sum(jnp.square(a))) for a in leaves],
            [a.reshape(-1)[i] for a, i in zip(leaves, index)])


def _fetched(read) -> tuple:
    norms, samples = jax.device_get(read)
    return [float(v) for v in norms], [np.asarray(a) for a in samples]


def build_step(cell, devices, dtype=None):
    """The cell's round step, built and compiled: (step, compile info)."""
    prog = program.RoundStep(cell.config, cell.mix, cell.workload["method"],
                             devices, weights(cell.config), dtype=dtype)
    m = cell.mix
    shape = (m.clients, m.local_steps, m.batch, m.seq_len)
    info = prog.compile({k: jax.ShapeDtypeStruct(shape, jnp.int32)
                         for k in ("tokens", "labels")})
    log(f"round step compiled in {info['compile_s']:.1f} s; compiled "
        f"per device: arguments {info.get('argument_bytes')}, temporaries "
        f"{info.get('temp_bytes')}, peak {info.get('peak_bytes')} bytes")
    return prog, info


def check_rounds(prog, cell, seed):
    """Make the state from the seed and drive it through the check rounds
    by the window's feed and call: (state, traffic, the program's
    readings)."""
    key = common.seed_key(seed)
    index = sample_index(cell.config, seed)
    traffic = RoundTraffic(cell.mix, cell.config["vocab_size"], seed)
    state = prog.init_state(key)
    losses, grad = [], None
    for r in range(CHECK_ROUNDS):
        state, loss, _ = _round(prog, state, traffic, r)
        losses.append(loss)
        if r == 0:
            grad = _fetched(prog.first_grad(state, read_leaves, index))
    change = _fetched(prog.change(state, key, read_leaves, index))
    return state, traffic, _readings(losses, grad, change, cell)


def _readings(losses, grad, change, cell) -> dict:
    return {"losses": losses, "grad": grad[0], "grad_sample": grad[1],
            "change": change[0], "change_sample": change[1],
            "sizes": leaf_sizes(cell.config)}


def reference_side(cell, devices, seed, precision=None):
    """The plain reference's readings of the check rounds, at the
    configuration's matmul precision (or at ``precision``)."""
    conf = cell.config
    ref = _reference(conf)
    make = weights(conf)
    key = common.seed_key(seed)
    traffic = RoundTraffic(cell.mix, conf["vocab_size"], seed)
    method = cell.workload["method"]
    run = SavicReference(
        lambda p, t, l: ref.loss(conf, p, t, l), gamma=method["gamma"],
        beta1=method["beta1"], alpha=method["alpha"], beta2=method["beta2"],
        devices=devices)
    dt = jnp.dtype(conf["dtype"])
    start = lambda k: jax.tree.map(lambda a: a.astype(dt), make(k))
    index = sample_index(conf, seed)
    read = jax.jit(read_leaves)

    def batch_at(r):
        b = traffic.round(r)
        return b["tokens"], b["labels"]

    with jax.default_matmul_precision(precision or conf["matmul_precision"]):
        params = jax.jit(start, out_shardings=getattr(
            run, "everywhere",
            jax.sharding.SingleDeviceSharding(devices[0])))(key)
        losses, grad, x = run.run(
            params, batch_at, CHECK_ROUNDS,
            lambda g: _fetched(read(jax.tree.map(jnp.abs, g), index)))
        change = _fetched(jax.jit(lambda x, k, i: read_leaves(jax.tree.map(
            lambda a, b: a - b, x, start(k)), i))(x, key, index))
    return _readings(losses, grad, change, cell)


def run_cell(cell, seed, seconds, want_trace, devices, t_start, peaks):
    """One run; returns the result object the harness prints."""
    log(f"devices ready at {time.time() - t_start:.2f} s")
    prog, _ = build_step(cell, devices)
    log(f"step built at {time.time() - t_start:.2f} s")
    state, traffic, prog_read = check_rounds(prog, cell, seed)
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.2f} s; check-round losses {prog_read['losses']}")

    times, losses = [], []
    r = CHECK_ROUNDS
    t0 = time.perf_counter()
    while True:
        state, loss, dt = _round(prog, state, traffic, r)
        times.append(dt)
        losses.append(loss)
        r += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    failed = sum(not math.isfinite(x) for x in losses)
    tokens_per_s = len(times) * cell.mix.tokens_per_round / elapsed
    median = float(np.median(times))
    slow = [(i, round(1e3 * t, 1)) for i, t in enumerate(times)
            if t > 1.5 * median]
    round_ms_p90 = 1e3 * float(np.percentile(times, 90))
    log(f"window {elapsed:.3f} s, {len(times)} rounds, {tokens_per_s:.1f} "
        f"tokens/s; round from dispatch to loss: median "
        f"{1e3 * median:.2f} ms, p90 {round_ms_p90:.3f} ms, max "
        f"{1e3 * max(times):.2f} ms; outside it "
        f"{elapsed - sum(times):.3f} s; rounds over 1.5x the median "
        f"(index, ms): {slow}")
    # (the CPU of the tests reports no memory statistics)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    events = None
    if want_trace:
        n = int(min(max(TRACE_SECONDS / median,
                        TRACE_ROUNDS[0]), TRACE_ROUNDS[1]))
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                for _ in range(n):
                    state, _, _ = _round(prog, state, traffic, r)
                    r += 1
            jax.profiler.stop_trace()
            pd = jax.profiler.ProfileData.from_file(trace.find_xplane(logdir))
            events = trace.from_profile(pd, [d.id for d in devices])
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        log(f"traced {n} rounds over {trace.window_s(events):.3f} s")

    del state, prog
    gc.collect()
    ref_read = reference_side(cell, devices, seed)
    limits = cell.workload["check"]["limits"]
    nums = check.numbers(prog_read, ref_read)
    correct = check.verdict(nums, limits) and failed == 0
    log(f"program losses {prog_read['losses']}, reference "
        f"{ref_read['losses']}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(times),
              "failed": int(failed)}
    if want_trace:
        busy = trace.busy_s(events)
        device.update(busy_s=sum(busy) / len(busy),
                      window_s=trace.window_s(events))
        ctx = types.SimpleNamespace(
            events=events, config=cell.config, seq_len=cell.mix.seq_len,
            tokens_per_s=tokens_per_s, chips=len(devices), peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": trace.top_device_ops(events),
                               "idle_gaps": trace.idle_gaps(events)}
    else:
        values = {"setup_s": setup_s, "train_tokens_per_s": tokens_per_s,
                  "round_ms_p90": round_ms_p90}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = check.as_json(nums, limits)
    for line in check.lines(nums, limits):
        print(line, file=sys.stderr, flush=True)
    return result
