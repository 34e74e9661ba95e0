"""A model type is two files: ``model_types/<type>.py`` and
``reference/<type>.py``, found by name. A type written only as those two
files loads through ``spec``, ``program``, ``harness.weights`` and
``flops``, and the types the benchmark has give what they gave when their
facts sat in tables of the harness: the same weight tree, leaf for leaf,
and the same seeded weights (``test_flops.py`` holds their FLOPs a token
to the numbers worked out by hand)."""
import inspect
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import flops, harness, program, spec
from benchmarks.chip.reference import common
from benchmarks.chip.tests import tiny

SEED = 2 ** 31 + 17


def _conf(name):
    with open(os.path.join(tiny.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _new_type(tmp_path, kind):
    """A copy of qwen2's two files under the name ``kind``, in a directory
    of their own."""
    here = str(tmp_path)
    for sub in ("model_types", "reference"):
        os.makedirs(os.path.join(here, sub))
        with open(os.path.join(tiny.HERE, sub, "qwen2.py")) as f:
            text = f.read()
        with open(os.path.join(here, sub, f"{kind}.py"), "w") as f:
            f.write(text)
    return here


def test_a_new_model_type_loads_from_two_new_files(tmp_path, monkeypatch):
    base = tiny.cell("qwen2").config
    conf = dict(base, model_type="dense_gqa")
    key = common.seed_key(SEED)
    want = harness.weights(base)(key)
    want_cfg, want_sizes = program.model_config(base), harness.leaf_sizes(base)
    want_flops = flops.train_flops_per_token(base, 64)
    with pytest.raises(SystemExit, match="no model_types file"):
        spec.model_type("dense_gqa")
    monkeypatch.setattr(spec, "TYPES_DIR", _new_type(tmp_path, "dense_gqa"))
    assert spec.model_type("dense_gqa").DEPTH == "num_hidden_layers"
    assert callable(spec.reference("dense_gqa").loss)
    assert program.model_config(conf) == want_cfg
    got = harness.weights(conf)(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    assert harness.leaf_sizes(conf) == want_sizes
    assert flops.train_flops_per_token(conf, 64) == want_flops


def test_no_model_type_is_named_in_the_general_modules():
    for mod in (spec, program, harness, flops):
        text = inspect.getsource(mod)
        assert "qwen2" not in text and "mamba2" not in text, mod.__name__


# (path, shape) of every leaf, in tree order, as the benchmark's weights and
# the program's parameter tree had them before the types had files
TREES = {
    "qwen2-0.5b-l16": [
        ("blocks/stack/attn/wk/b", (16, 2, 64)),
        ("blocks/stack/attn/wk/w", (16, 896, 2, 64)),
        ("blocks/stack/attn/wo/w", (16, 14, 64, 896)),
        ("blocks/stack/attn/wq/b", (16, 14, 64)),
        ("blocks/stack/attn/wq/w", (16, 896, 14, 64)),
        ("blocks/stack/attn/wv/b", (16, 2, 64)),
        ("blocks/stack/attn/wv/w", (16, 896, 2, 64)),
        ("blocks/stack/ffn/wd/w", (16, 4864, 896)),
        ("blocks/stack/ffn/wg/w", (16, 896, 4864)),
        ("blocks/stack/ffn/wu/w", (16, 896, 4864)),
        ("blocks/stack/norm1/scale", (16, 896)),
        ("blocks/stack/norm2/scale", (16, 896)),
        ("embed/table", (153600, 896)),
        ("final_norm/scale", (896,))],
    "mamba2-1.3b-l7": [
        ("blocks/stack/mamba/A_log", (7, 64)),
        ("blocks/stack/mamba/Dskip", (7, 64)),
        ("blocks/stack/mamba/conv_B", (7, 128, 4)),
        ("blocks/stack/mamba/conv_C", (7, 128, 4)),
        ("blocks/stack/mamba/conv_x", (7, 4096, 4)),
        ("blocks/stack/mamba/dt_bias", (7, 64)),
        ("blocks/stack/mamba/gate_norm/scale", (7, 4096)),
        ("blocks/stack/mamba/wB/w", (7, 2048, 128)),
        ("blocks/stack/mamba/wC/w", (7, 2048, 128)),
        ("blocks/stack/mamba/wdt/w", (7, 2048, 64)),
        ("blocks/stack/mamba/wo/w", (7, 4096, 2048)),
        ("blocks/stack/mamba/wx/w", (7, 2048, 4096)),
        ("blocks/stack/mamba/wz/w", (7, 2048, 4096)),
        ("blocks/stack/norm1/scale", (7, 2048)),
        ("embed/head", (2048, 51200)),
        ("embed/table", (51200, 2048)),
        ("final_norm/scale", (2048,))],
}
PARAMS = {"qwen2-0.5b-l16": 376_224_640, "mamba2-1.3b-l7": 390_631_744}
# the sum of |w| over the seeded weights at the tiny size
TINY_ABS_SUM = {"qwen2": 20157.38671875, "mamba2": 37007.359375}


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_weight_trees_stay_leaf_for_leaf(name):
    conf = _conf(name)
    make = harness.weights(conf)      # checks the program's tree against it
    shapes = jax.eval_shape(make, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    assert [("/".join(str(k.key) for k in p), s.shape) for p, s in leaves] \
        == TREES[name]
    assert all(s.dtype == jnp.float32 for _, s in leaves)
    assert sum(math.prod(s) for _, s in TREES[name]) == PARAMS[name]
    assert harness.leaf_names(conf) == [p for p, _ in TREES[name]]


@pytest.mark.parametrize("kind", sorted(TINY_ABS_SUM))
def test_the_seeded_weights_stay(kind):
    params = harness.weights(tiny.cell(kind).config)(common.seed_key(SEED))
    total = sum(jnp.sum(jnp.abs(a)) for a in jax.tree.leaves(params))
    assert float(total) == TINY_ABS_SUM[kind]
