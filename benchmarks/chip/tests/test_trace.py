"""The trace reduction: host spans read from a trace recorded here on the
CPU, and the device arithmetic (busy time, idle gaps, exposed collectives,
top operations, the per-layer readers) on events written out by hand."""
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import spec, trace


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            for name in trace.HOST_SPANS:
                with jax.profiler.TraceAnnotation(name):
                    if name == "read_loss":
                        float(f(x))
    jax.profiler.stop_trace()
    return jax.profiler.ProfileData.from_file(trace.find_xplane(logdir))


def test_host_spans_of_a_recorded_trace(cpu_profile):
    spans = trace.host_events(cpu_profile,
                              (trace.WINDOW_SPAN,) + trace.HOST_SPANS)
    names = [n for _, _, n in spans]
    assert names.count(trace.WINDOW_SPAN) == 1
    for name in trace.HOST_SPANS:
        assert names.count(name) == 3
    (lo, hi), = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    assert all(lo <= s <= e <= hi for s, e, n in spans)


def test_a_trace_without_the_chips_plane_is_refused(cpu_profile):
    with pytest.raises(ValueError, match="no plane /device:TPU:0"):
        trace.from_profile(cpu_profile, [0])


AR = "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %p), to_apply=%add"


def _events():
    f1 = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    f2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop"
    return trace.Events(
        window=(0.0, 10.0),
        device_ops=[
            [(1.0, 3.0, f1), (2.0, 4.0, f2), (5.0, 6.0, AR),
             (5.5, 7.0, f1), (9.5, 11.0, f2),
             (1.0, 2.5, "%while.3 = (s32[], f32[8]{0}) while((s32[], "
                        "f32[8]{0}) %t), condition=%c, body=%b")],
            [(0.5, 2.5, f1), (5.0, 8.0, AR)],
        ],
        host_spans=[(0.0, 1.0, "make_batch"), (4.0, 5.2, "read_loss"),
                    (7.0, 9.6, "dispatch")])


def test_busy_time_is_the_union_inside_the_window():
    ev = _events()
    assert trace.window_s(ev) == 10.0
    # chip 0: [1,4] + [5,7] + [9.5,10] = 5.5; chip 1: [0.5,2.5] + [5,8] = 5
    assert trace.busy_s(ev) == [5.5, 5.0]


def test_top_device_ops_average_over_chips():
    top = dict(trace.top_device_ops(_events()))
    # fusion.1: (2 + 1.5 + 2) / 2; fusion.2: (2 + 0.5) / 2; all-reduce:
    # (1 + 3) / 2; the loop holds fusion.1 and is not counted
    assert top == {"fusion.1": 2.75, "fusion.2": 1.25, "all-reduce.7": 2.0}


def test_idle_gaps_are_named_by_the_host_span_over_them():
    gaps = trace.idle_gaps(_events())
    # chip 0 idles [0,1] (make_batch), [4,5] (read_loss), [7,9.5]
    # (dispatch), longest first
    assert gaps == [["dispatch", 2.5], ["make_batch", 1.0],
                    ["read_loss", 1.0]]


def test_readers():
    ev = _events()
    ctx = types.SimpleNamespace(
        events=ev, config={"model_type": "qwen2", "hidden_size": 896,
                           "num_attention_heads": 14,
                           "num_key_value_heads": 2,
                           "intermediate_size": 4864,
                           "num_hidden_layers": 16, "vocab_size": 151936},
        seq_len=512, tokens_per_s=1e4, chips=2,
        peaks={"bf16_flops_per_s": 197e12})
    idle = spec.metric_reader("device_idle_pct.train")(ctx)
    assert idle == pytest.approx(100 * (1 - 5.25 / 10))
    mfu = spec.metric_reader("mfu_pct.train")(ctx)
    assert mfu == pytest.approx(100 * 2_292_240_384 * 1e4 / (2 * 197e12))
    ctx.events = None
    assert spec.metric_reader("device_idle_pct.train")(ctx) is None


@pytest.mark.parametrize("event, kind", [
    (AR, ("all-reduce", "")),
    ("%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %p), "
     "to_apply=%add", ("all-reduce", "-start")),
    ("%ag-done = (f32[8]{0}, f32[32]{0}) all-gather-done((f32[8]{0}, "
     "f32[32]{0}) %ag-start)", ("all-gather", "-done")),
    ("%collective-permute.1 = f32[8]{0} collective-permute(f32[8]{0} %p), "
     "source_target_pairs={{0,1}}", ("collective-permute", "")),
    ("reduce-scatter.4", ("reduce-scatter", "")),
    ("all-to-all-start", ("all-to-all", "-start")),
    # a fusion that reads an all-reduce's result is no collective
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.7), kind=kLoop",
     None),
    ("all-reducer.1", None),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", None),
])
def test_collective_by_opcode_or_name(event, kind):
    assert trace.collective(event) == kind


def _collective_events():
    f = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop".format
    start = ("%all-reduce-start.5 = f32[8]{0} all-reduce-start(f32[8]{0} "
             "%p), to_apply=%add")
    done = ("%all-reduce-done.5 = f32[8]{0} all-reduce-done(f32[8]{0} "
            "%all-reduce-start.5)")
    permute = ("%collective-permute.1 = f32[8]{0} collective-permute("
               "f32[8]{0} %p), source_target_pairs={{0,1}}")
    gather = ("%all-gather.2 = f32[32]{0} all-gather(f32[8]{0} %p), "
              "dimensions={0}")
    loop = ("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
            "condition=%c, body=%b")
    return trace.Events(
        window=(0.0, 10.0),
        device_ops=[
            [(0.0, 10.0, loop),
             # overlapped on [2, 3] by fusion.1: exposed 1
             (1.0, 3.0, f(1)), (2.0, 4.0, AR),
             # in flight from 5 to 7, compute on [5.1, 6]: exposed 1.1
             (5.0, 5.1, start), (5.1, 6.0, f(2)), (6.5, 7.0, done),
             # hidden under fusion.3: exposed 0
             (7.9, 9.0, f(3)), (8.0, 8.5, permute)],
            # exposed whole, the second past the window's end by 0.5
            [(1.0, 2.0, gather), (9.5, 10.5, "reduce-scatter.4"),
             (2.0, 3.0, f(4))],
            # no collective on this chip
            [(1.0, 2.0, f(5))],
        ],
        host_spans=[(0.0, 0.1, "dispatch"), (5.0, 5.1, "dispatch"),
                    (10.0, 10.1, "dispatch")])


def test_exposed_collectives_per_chip():
    got = trace.exposed_collective_s(_collective_events())
    assert got[0] == pytest.approx(2.1)
    assert got[1] == pytest.approx(1.5)
    assert got[2] is None
    assert trace.rounds(_collective_events()) == 2


def test_the_collective_reader():
    read = spec.metric_reader("collective_exposed_ms.train")
    ev = _collective_events()
    # (2.1 + 1.5 + 0) s over 3 chips, 2 rounds
    assert read(types.SimpleNamespace(events=ev)) == pytest.approx(600.0)
    # one chip, no collective: nothing to read
    ev.device_ops = ev.device_ops[2:]
    assert read(types.SimpleNamespace(events=ev)) is None
    assert read(types.SimpleNamespace(events=None)) is None
    # collectives, no round in the window
    ev = _collective_events()
    ev.host_spans = []
    assert read(types.SimpleNamespace(events=ev)) is None
