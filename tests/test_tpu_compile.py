"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU runs a kernel's body but never asks Mosaic about
its block shapes or its VMEM use; this file does, at qwen2-0.5b widths,
without a chip: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached. Nothing runs, so these
tests say nothing about results or times — ``tests/test_fused_step.py`` and
``tests/test_serve.py`` pin the math.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under several test
workers only the worker given this file may try.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_step, flash_attention, fused_ce
from repro.kernels import quantize_update
from repro.kernels import scaled_update, sync_average
from repro.models.layers import padded_vocab

CFG = get_config("qwen2-0.5b")
HEAD_DIM = CFG.d_model // CFG.n_heads          # 64
M = 2                                          # clients on one chip
LEAF = CFG.d_model * CFG.d_ff                  # one MLP matrix


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2; the persistent compilation cache
    is off meanwhile (a compile for a described chip is written to it but
    cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def n_params():
    """qwen2-0.5b's parameter count: the length of one client's flat
    buffer on the fused local step."""
    from repro.models import ModelCallConfig, build
    shapes = jax.eval_shape(build(CFG, ModelCallConfig()).init,
                            jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


def _compile_text(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the compiled HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("scaling", ["global-const", "local-debias"])
def test_fused_step_flat_compiles(one_chip, n_params, scaling):
    kw = dict(gamma=3e-3, beta1=0.9, alpha=1e-2, beta2=0.999, kind="adam")
    n = n_params
    if scaling == "global-const":
        fn = lambda p, m, g, d: scaled_update.fused_step_flat(
            p, m, g, d, schedule="const", **kw)[:2]
        shapes = [((M, n), F32)] * 3 + [((n,), F32)]
    else:
        fn = lambda p, m, g, d, t: scaled_update.fused_step_flat(
            p, m, g, d, None, t, schedule="debias", update_d=True, **kw)
        shapes = [((M, n), F32)] * 4 + [((M,), I32)]
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("cache_len", [144, 2048])
def test_decode_attention_compiles(one_chip, cache_len):
    B, H, Hk, D = 4, CFG.n_heads, CFG.n_kv_heads, HEAD_DIM
    text = _compile_text(decode_step.decode_attention, one_chip,
                         ((B, H, D), F32), ((B, cache_len, Hk, D), F32),
                         ((B, cache_len, Hk, D), F32), ((B, cache_len), F32))
    assert "tpu_custom_call" in text


def test_decode_sample_compiles(one_chip):
    B, d, V = 4, CFG.d_model, padded_vocab(CFG.vocab_size)
    fn = lambda y, table, noise: decode_step.decode_sample(
        y, table, noise, scale=d ** -0.5, v_real=CFG.vocab_size)
    text = _compile_text(fn, one_chip, ((B, d), F32), ((V, d), F32),
                         ((B, V), F32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    S = 512
    q = ((1, CFG.n_heads, S, HEAD_DIM), F32)
    kv = ((1, CFG.n_kv_heads, S, HEAD_DIM), F32)
    text = _compile_text(flash_attention.flash_attention_bhsd, one_chip,
                         q, kv, kv)
    assert "tpu_custom_call" in text


def test_quantize_update_compiles(one_chip):
    text = _compile_text(quantize_update.quantize_update_flat, one_chip,
                         *[((LEAF,), F32)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("leaf", ["ffn-layer-major", "table-in-place"])
def test_sync_average_compiles(one_chip, leaf):
    """The one-pass sync at qwen2-0.5b widths (16 layers, M = 2): an FFN
    stack read layer-major from an (L, M, …) array, as the client loop
    carries it, and the padded embedding written over its own buffer (the
    whole input aliased to the output). Neither copies its input."""
    if leaf == "ffn-layer-major":
        shape, layer_major = (16, M, CFG.d_model, CFG.d_ff), True
        view = lambda x: jnp.swapaxes(x, 0, 1)
    else:
        shape = (M, padded_vocab(CFG.vocab_size), CFG.d_model)
        layer_major, view = False, lambda x: x
    fn = lambda x, w: sync_average.sync_average(view(x), w, drift=True,
                                                layer_major=layer_major)
    args = [jax.ShapeDtypeStruct(shape, F32, sharding=one_chip),
            jax.ShapeDtypeStruct((M,), F32, sharding=one_chip)]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " copy(" not in text.split("ENTRY")[1]
    if not layer_major:
        assert compiled.memory_analysis().alias_size_in_bytes \
            == 4 * math.prod(shape)


@pytest.mark.parametrize("head", ["tied-qwen2", "untied-mamba2"])
def test_fused_cross_entropy_compiles(one_chip, head):
    """The fused LM head and cross-entropy, forward and backward under
    ``vmap`` over M = 2 clients, at the one-chip cells' shapes: qwen2-0.5b's
    tied 153,600-row table at 512 tokens, mamba2-1.3b's untied (2048,
    51,200) head at 4,096. Both kernels stay within their VMEM limit and
    the round step's temporaries hold no (T, V) logits."""
    if head == "tied-qwen2":
        cfg, T = CFG, 512
    else:
        cfg, T = get_config("mamba2-1.3b"), 4096
    tied, d, V = cfg.tie_embeddings, cfg.d_model, padded_vocab(cfg.vocab_size)
    fn = jax.vmap(jax.value_and_grad(
        lambda h, w, y: fused_ce.fused_cross_entropy(
            h, w, y, tied=tied, scale=d ** -0.5, v_real=cfg.vocab_size),
        (0, 1)))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((M, T, d), F32), ((M, V, d) if tied else (M, d, V), F32),
        ((M, T), I32))]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < M * T * V * 4 / 8
