"""The program's named scopes, read back: every scope a cell's readers use
names instructions of the tiny cells' compiled round step, and the device
time a round under a scope comes out of events written out by hand."""
import sys
import types

import jax
import pytest

from benchmarks.chip import harness, scopes, spec, trace
from benchmarks.chip.tests import tiny

# reader -> the scope it reads
READS = {"model_ms.train": "model", "local_step_ms.train": "local_step",
         "sync_ms.train": "sync", "server_ms.train": "server",
         "attention_ms.train": "attention", "ssd_scan_ms.train": "ssd_scan",
         "lm_head_ms.train": "lm_head"}
ENGINE = ("model", "local_step", "sync", "server")
LAYER = {"qwen2": "attention", "mamba2": "ssd_scan"}


@pytest.fixture(scope="module", params=sorted(LAYER))
def compiled(request):
    cell = tiny.cell(request.param)
    prog, _ = harness.build_step(cell, jax.devices()[:1])
    return request.param, cell, scopes.op_scopes(prog.compiled.as_text())


def test_every_scope_a_cell_reads_names_instructions(compiled):
    kind, cell, ops = compiled
    read = {READS[m["name"]] for m in cell.per_layer if m["name"] in READS}
    assert read == set(ENGINE) | {"lm_head", LAYER[kind]}
    for scope in read:
        assert any(scopes.in_scope(op, scope) for op in ops.values()), scope
    # the layer's backward (under the transpose of the model's jvp) and its
    # recomputation by the checkpointed layer scan are in the scope too
    layer = [op for op in ops.values() if scopes.in_scope(op, LAYER[kind])]
    assert any("transpose(jvp(" in op for op in layer)
    assert any("rematted_computation" in op for op in layer)
    assert any("transpose(jvp(lm_head))" in op for op in ops.values())


def test_no_instruction_is_under_two_engine_scopes(compiled):
    _, _, ops = compiled
    for name, op in ops.items():
        assert sum(scopes.in_scope(op, s) for s in ENGINE) <= 1, (name, op)
    # the model's parts lie under no other engine scope
    for part in ("attention", "ssd_scan", "lm_head"):
        for op in ops.values():
            if scopes.in_scope(op, part):
                assert not any(scopes.in_scope(op, s) for s in ENGINE[1:]), op


@pytest.mark.parametrize("op_name, scope, inside", [
    ("jit(f)/client_loop/model/jvp(lm_head)/dot_general", "lm_head", True),
    ("jit(f)/model/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", "attention", True),
    ("jit(f)/vmap(model)/transpose(jvp(attention))/mul", "attention", True),
    ("jit(f)/vmap(model)/transpose(jvp(attention))/mul", "model", True),
    ("jit(f)/while/body/closed_call/local_step/update/sub", "model", False),
    ("jit(f)/modeling/add", "model", False),
    ("jit(f)/model_ms/add", "model", False),
    ("", "model", False),
])
def test_in_scope(op_name, scope, inside):
    assert scopes.in_scope(op_name, scope) is inside


def test_op_scopes_reads_every_computation():
    text = "\n".join([
        "HloModule jit_round_step",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        '  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(f)/sync/mul" stack_frame_id=2}',
        "}",
        # a dot rewritten as a convolution, which lost its metadata
        "%fused_computation.2 (param_0: f32[8]) -> f32[8] {",
        "  %fusion.4 = f32[8]{0} fusion(%param_0), kind=kLoop, "
        "calls=%fused_computation.1",
        '  %add.5 = f32[8]{0} add(%fusion.4, %param_0), '
        'metadata={op_name="jit(f)/sync"}',
        "  ROOT %convolution.6 = f32[8]{0} convolution(%add.5, %fusion.4)",
        "}",
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        # fusions without an op_name of their own take the deepest one
        # found in what they call
        "  %copy_fusion = f32[8]{0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.2",
        '  ROOT %fusion.1.remat = f32[8]{0} fusion(%copy_fusion), '
        'kind=kLoop, calls=%fused_computation.1, metadata={op_type="x" '
        'op_name="jit(f)/server/mul"}',
        "}"])
    assert scopes.op_scopes(text) == {
        "mul.3": "jit(f)/sync/mul", "fusion.4": "jit(f)/sync/mul",
        "add.5": "jit(f)/sync", "convolution.6": "", "p": "",
        "copy_fusion": "jit(f)/sync/mul",
        "fusion.1.remat": "jit(f)/server/mul"}


def _hlo(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop"


OPS = {
    "fusion.1": "jit(f)/while/body/closed_call/vmap(model)/jvp(lm_head)/dot",
    "fusion.2": "jit(f)/while/body/closed_call/vmap(model)/transpose(jvp())/"
                "while/body/closed_call/checkpoint/rematted_computation/"
                "attention/dot_general",
    "fusion.3": "jit(f)/sync/reduce_sum",
    "while.4": "jit(f)/while/body/closed_call/vmap(model)/jvp()/while",
    "copy.5": "",
    "fusion.6": "jit(f)/modeling/add",
}


def _events():
    loop = ("%while.4 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
            "condition=%c, body=%b")
    return trace.Events(
        window=(0.0, 10.0),
        device_ops=[
            [(1.0, 5.0, loop), (1.0, 2.0, _hlo("fusion.1")),
             (2.0, 4.0, _hlo("fusion.2")), (5.0, 6.0, _hlo("fusion.3")),
             (6.0, 7.0, _hlo("copy.5")), (7.0, 8.0, _hlo("fusion.6")),
             (9.5, 11.0, _hlo("fusion.1"))],
            [(1.0, 3.0, _hlo("fusion.1"))],
        ],
        host_spans=[(-1.0, -0.5, "dispatch"), (0.0, 0.1, "make_batch"),
                    (0.2, 0.4, "dispatch"), (5.0, 5.1, "dispatch"),
                    (5.1, 5.2, "read_loss")])


def test_scope_ms_counts_wrapped_and_nested_names_once_per_round():
    ev = _events()
    # two rounds start in the window; two chips. model: chip 0 fusion.1
    # 1 + 0.5 (clipped) and fusion.2 2, the loop holding them not again;
    # chip 1 fusion.1 2 -> 5.5 s / 2 chips / 2 rounds
    assert scopes.scope_ms(ev, OPS, "model") == pytest.approx(1375.0)
    assert scopes.scope_ms(ev, OPS, "attention") == pytest.approx(500.0)
    assert scopes.scope_ms(ev, OPS, "lm_head") == pytest.approx(875.0)
    assert scopes.scope_ms(ev, OPS, "sync") == pytest.approx(250.0)
    # a scope no instruction of the program is in (the parent's program
    # names none): nothing to read
    assert scopes.scope_ms(ev, OPS, "server") is None
    ev.host_spans = [s for s in ev.host_spans if s[2] != "dispatch"]
    assert scopes.scope_ms(ev, OPS, "model") is None


def test_readers():
    ctx = types.SimpleNamespace(events=_events(), scopes=OPS)
    expect = {"model": 1375.0, "attention": 500.0, "lm_head": 875.0,
              "sync": 250.0}
    for name, scope in READS.items():
        read = spec.metric_reader(name)
        assert read(ctx) == (pytest.approx(expect[scope]) if scope in expect
                             else None), name
        assert read(types.SimpleNamespace(events=_events())) is None
        assert read(types.SimpleNamespace(events=None, scopes=OPS)) is None


def test_a_reader_compiles_the_step_of_the_cell_this_process_runs(
        compiled, monkeypatch):
    kind, cell, ops = compiled
    name = tiny.CELLS[kind]
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", name,
                                      "--seed", "3", "--trace", "1"])
    monkeypatch.setattr(spec, "load_cell", lambda root, n: {name: cell}[n])
    inst = sorted(n for n, op in ops.items() if scopes.in_scope(op, "model")
                  and not trace.CONTAINER.match(n))[0]
    ctx = types.SimpleNamespace(events=trace.Events(
        window=(0.0, 1.0), device_ops=[[(0.0, 0.5, _hlo(inst))]],
        host_spans=[(0.0, 0.1, "dispatch")]))
    assert spec.metric_reader("model_ms.train")(ctx) == pytest.approx(500.0)
    # the step compiled again names its instructions as the harness's did,
    # and the map stays on ctx for the other readers: sync is named, and
    # ran for no time in this trace
    assert ctx.scopes == ops
    monkeypatch.setattr(spec, "load_cell", None)
    assert spec.metric_reader("sync_ms.train")(ctx) == 0.0
