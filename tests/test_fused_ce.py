"""The fused LM head and cross-entropy (kernels/fused_ce.py,
models/layers.py:unembed_cross_entropy): the kernel pair, interpreted on the
CPU, against ``cross_entropy(unembed(...))`` in the loss, dh and dW; the rule
that picks the path, and what the step builder records of it; the scope its
ops carry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh

from benchmarks.chip import scopes
from repro.configs import ShapeConfig, get_config
from repro.kernels import fused_ce
from repro.launch import steps
from repro.models.layers import (cross_entropy, head_path, padded_vocab,
                                 unembed, unembed_cross_entropy)

D, V_REAL = 64, 2000             # padded to 2048 rows: 48 never carry a token
V = padded_vocab(V_REAL)
TWO_TOKEN_TILES = fused_ce.Tiles((16, 512), (16, 512))


def _cfg(tied):
    return get_config("qwen2-0.5b", reduced=True).replace(
        d_model=D, vocab_size=V_REAL, tie_embeddings=tied)


def _inputs(key, tied, B=2, S=16, masked="some"):
    k = jax.random.split(key, 4)
    p = {"table": jax.random.normal(k[0], (V, D)) * 0.5}
    if not tied:
        p["head"] = jax.random.normal(k[1], (D, V)) * D ** -0.5
    x = jax.random.normal(k[2], (B, S, D))
    y = jax.random.randint(k[3], (B, S), 0, V_REAL)
    if masked == "some":          # scattered labels < 0 and a whole row
        y = y.at[0, ::5].set(-1).at[1].set(-1)
    elif masked == "all":
        y = jnp.full_like(y, -1)
    return p, x, y


def _loss_fns(cfg, tile):
    w = "table" if cfg.tie_embeddings else "head"

    def ref(p, x, y):
        return cross_entropy(unembed(p, x, cfg, jnp.float32), y,
                             cfg.vocab_size)

    def fused(p, x, y):
        return fused_ce.fused_cross_entropy(
            x.reshape(y.size, D), p[w], y.reshape(-1),
            tied=cfg.tie_embeddings, scale=D ** -0.5, v_real=V_REAL,
            mxu_dtype=jnp.float32, tile=tile, interpret=True)
    return ref, fused


CASES = {
    "one_tile": dict(tile=None, masked="some", g=1.0, vmap=False),
    "two_token_tiles": dict(tile=TWO_TOKEN_TILES, masked="some", g=1.0,
                            vmap=False),
    "cotangent": dict(tile=TWO_TOKEN_TILES, masked="some", g=-2.75,
                      vmap=False),
    "all_masked": dict(tile=TWO_TOKEN_TILES, masked="all", g=1.0,
                       vmap=False),
    "vmap_clients": dict(tile=TWO_TOKEN_TILES, masked="some", g=0.5,
                         vmap=True),
}


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_matches_unembed_cross_entropy(tied, case):
    """Loss, dh and dW of the kernel pair against the jnp head, with padded
    vocab rows, masked labels and the cotangent ``g``; under ``vmap`` each
    of M=2 clients has its own weights, hidden states and labels."""
    c = CASES[case]
    cfg = _cfg(tied)
    ref, fused = _loss_fns(cfg, c["tile"])
    S = 64 if c["tile"] is None else 16   # one tile holds all 128 tokens
    if c["vmap"]:
        keys = jax.random.split(jax.random.PRNGKey(7), 2)
        args = jax.tree.map(lambda *a: jnp.stack(a), *[
            _inputs(k, tied, S=S, masked=c["masked"]) for k in keys])
    else:
        args = _inputs(jax.random.PRNGKey(3), tied, S=S, masked=c["masked"])
    if c["tile"] is None:
        assert fused_ce.tiles(args[1].size // D, V, D) is not None

    def value_and_grad(f):
        vg = jax.value_and_grad(lambda p, x, y: c["g"] * f(p, x, y), (0, 1))
        return jax.jit(jax.vmap(vg) if c["vmap"] else vg)(*args)

    (l_f, (gp_f, gx_f)), (l_r, (gp_r, gx_r)) = map(value_and_grad,
                                                   (fused, ref))
    np.testing.assert_allclose(l_f, l_r, rtol=2e-6, atol=1e-6)
    w = "table" if tied else "head"
    for got, want in ((gx_f, gx_r), (gp_f[w], gp_r[w])):
        scale = float(jnp.abs(want).max()) or 1.0
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * scale)
    if not tied:                  # the table takes no gradient from the head
        assert not np.any(gp_f["table"])
    if c["masked"] == "all":
        assert float(jnp.abs(l_f).max()) == 0.0
        assert not np.any(gx_f) and not np.any(gp_f[w])


def test_padded_rows_take_no_gradient():
    p, x, y = _inputs(jax.random.PRNGKey(1), True)
    _, fused = _loss_fns(_cfg(True), TWO_TOKEN_TILES)
    g = jax.grad(fused)(p, x, y)["table"]
    assert not np.any(g[V_REAL:])
    assert np.all(np.any(g[:V_REAL] != 0, axis=1))


def test_tiles_rule():
    # cell 1 (qwen2 at 16 layers, 512 tokens) and cell 2 (mamba2, 4096)
    assert fused_ce.tiles(512, 153600, 896) is not None
    assert fused_ce.tiles(4096, 51200, 2048) is not None
    assert fused_ce.tiles(500, 153600, 896) is None        # T
    assert fused_ce.tiles(512, 153600 + 100, 896) is None  # V
    assert fused_ce.tiles(1 << 16, 51200, 2048) is None    # dh over VMEM
    for T, V_, d in ((512, 153600, 896), (4096, 51200, 2048)):
        tl = fused_ce.tiles(T, V_, d)
        assert fused_ce._fwd_bytes(*tl.fwd, d) <= fused_ce.VMEM_BUDGET
        assert fused_ce._bwd_bytes(T, *tl.bwd, d) <= fused_ce.VMEM_BUDGET


# --------------------------------------------------------------------------- #
# the rule that picks the path, and the step's meta
# --------------------------------------------------------------------------- #

QWEN = get_config("qwen2-0.5b")


@pytest.mark.parametrize("cfg, tokens, devices, backend, want", [
    (QWEN, 512, 1, "tpu", "fused"),
    (get_config("mamba2-1.3b"), 4096, 1, "tpu", "fused"),
    (QWEN, 8192, 4, "tpu", "jnp"),                    # a mesh of four
    (QWEN, 512, 1, "cpu", "jnp"),
    (QWEN.replace(logit_softcap=30.0), 512, 1, "tpu", "jnp"),
    (QWEN, 500, 1, "tpu", "jnp"),                     # T does not tile
], ids=["one_tpu", "one_tpu_untied", "mesh4", "cpu", "softcap", "no_tile"])
def test_head_path(cfg, tokens, devices, backend, want):
    assert head_path(cfg, tokens, devices=devices, backend=backend) == want


def test_head_path_keeps_a_raised_matmul_precision():
    with jax.default_matmul_precision("float32"):
        assert head_path(QWEN, 512, backend="tpu") == "jnp"
    assert head_path(QWEN, 512, backend="tpu") == "fused"


def test_cpu_loss_takes_the_jnp_head_bit_for_bit():
    cfg = _cfg(True)
    p, x, y = _inputs(jax.random.PRNGKey(5), True)
    assert head_path(cfg, y.size) == "jnp"
    got = jax.jit(lambda p, x, y: unembed_cross_entropy(
        p, x, y, cfg, jnp.float32))(p, x, y)
    want = jax.jit(lambda p, x, y: cross_entropy(
        unembed(p, x, cfg, jnp.float32), y, cfg.vocab_size))(p, x, y)
    assert float(got) == float(want)


@pytest.mark.parametrize("mesh_size, backend, want", [
    (1, "cpu", "jnp"), (1, "tpu", "fused"), (4, "tpu", "jnp")])
def test_step_meta_records_the_head(monkeypatch, mesh_size, backend, want):
    """``build_train_step`` records the path the loss of its step takes: on
    a TPU (the backend stood in for) the fused head for a mesh of one and
    the jnp head for a mesh of four; the model is built for the mesh's
    devices. Shapes only: nothing is lowered."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if mesh_size == 1:
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
    else:
        mesh = AbstractMesh((mesh_size, 1), ("data", "model"))
    built = []
    real_build = steps.build

    def build(cfg, call):
        built.append(call)
        return real_build(cfg, call)
    monkeypatch.setattr(steps, "build", build)
    shape = ShapeConfig("tiny_train", 128, mesh_size, "train")
    step = steps.build_train_step("qwen2-0.5b", shape, mesh, reduced=True,
                                  h_local=1)
    assert step.meta["head"] == want
    assert [c.devices for c in built] == [mesh_size]


# --------------------------------------------------------------------------- #
# the scope of the fused path's ops
# --------------------------------------------------------------------------- #


def test_fused_ops_lie_under_lm_head(monkeypatch):
    """With the fused path taken (the rule stood in for), every op of the
    kernel pair, forward and backward, is under ``lm_head`` and its
    ``fused_ce`` sub-scope in the compiled module's metadata."""
    from repro.models import layers
    monkeypatch.setattr(layers, "head_path", lambda *a, **k: "fused")
    cfg = _cfg(True)
    p, x, y = _inputs(jax.random.PRNGKey(2), True, S=64)

    def f(p, x, y):
        with jax.named_scope("model"):
            return unembed_cross_entropy(p, x, y, cfg, jnp.float32)
    text = jax.jit(jax.value_and_grad(f, (0, 1))).lower(
        p, x, y).compile().as_text()
    ops = scopes.op_scopes(text)
    fused = [op for op in ops.values() if scopes.in_scope(op, "fused_ce")]
    assert fused
    assert all(scopes.in_scope(op, "lm_head") for op in fused)
    assert any("transpose(" in op for op in fused)      # the backward
    assert not any(scopes.in_scope(op, "lm_head") and not
                   scopes.in_scope(op, "fused_ce") and "dot" in op
                   for op in ops.values())


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-1.3b"])
def test_round_step_with_the_fused_head(monkeypatch, arch):
    """A SAVIC round (M=2 clients vmapped, H=2 local steps scanned) of the
    reduced model, its loss through the kernel pair (the rule stood in
    for), against the same round through the jnp head: the tied qwen2 and
    the untied mamba2 head."""
    from repro.core import engine, savic
    from repro.core.preconditioner import PrecondConfig
    from repro.core.savic import SavicConfig
    from repro.models import ModelCallConfig, build, layers, sample_batch

    cfg = get_config(arch, reduced=True)
    model = build(cfg, ModelCallConfig(dtype=jnp.float32))
    spec = savic.engine_spec(PrecondConfig(kind="adam", alpha=1e-2),
                             SavicConfig(gamma=3e-3, beta1=0.9))
    state = engine.init_state(jax.random.PRNGKey(0), model.init, spec, 2)
    one = sample_batch(cfg, jax.random.PRNGKey(1), 2, 64)   # 128 tokens
    batch = jax.tree.map(lambda a: jnp.broadcast_to(
        a, (2, 2) + a.shape), one)

    def round_(path):
        monkeypatch.setattr(layers, "head_path", lambda *a, **k: path)
        step = jax.jit(engine.build_round_step(model.loss, spec))
        return step(state, batch, jax.random.PRNGKey(2))

    (s_f, m_f), (s_j, m_j) = round_("fused"), round_("jnp")
    np.testing.assert_allclose(m_f["loss"], m_j["loss"], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s_f["params"]),
                    jax.tree.leaves(s_j["params"])):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
