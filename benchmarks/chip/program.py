"""The system under test, as the benchmark drives it: the jitted round step
that ``repro.launch.train`` runs on one chip (``--mesh none``),
``repro.core.engine.build_round_step(model.loss, spec)`` under ``jax.jit``
with the state donated and a per-round key, built from a configuration
file, a traffic mix and a cell's method. This is the only module of the
benchmark that imports ``repro`` (besides ``faults.py``, which only the
check's tests and readings use).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import models as repro_models
from repro.configs import get_config
from repro.core import PrecondConfig, SavicConfig, engine, savic

# configuration-file key -> ModelConfig field, per model family
_FIELDS = {
    "qwen2": {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
              "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"},
    "mamba2": {"d_model": "d_model", "vocab_size": "vocab_size",
               "norm_epsilon": "norm_eps", "tie_embeddings": "tie_embeddings",
               "d_state": "ssm.d_state", "d_conv": "ssm.d_conv",
               "expand": "ssm.expand", "headdim": "ssm.head_dim",
               "ngroups": "ssm.ngroups", "chunk_size": "ssm.chunk"},
}
LAYERS_KEY = {"qwen2": "num_hidden_layers", "mamba2": "n_layer"}
# numbers that are no width, which the file sets on the registered model
SET = {"qwen2": {"rope_theta": "rope_theta"},
       "mamba2": {"norm_epsilon": "norm_eps", "vocab_size": "vocab_size"}}


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture cut to the file's depth, with the file's ``SET`` numbers.
    Every width the file states must be the program's, or this raises."""
    kind = conf["model_type"]
    prog = conf["program"]
    cfg = get_config(prog["arch"], reduced=prog["reduced"]).replace(
        n_layers=conf[LAYERS_KEY[kind]],
        **{field: conf[key] for key, field in SET[kind].items()})
    for key, field in _FIELDS[kind].items():
        have = cfg
        for part in field.split("."):
            have = getattr(have, part)
        if have != conf[key]:
            raise ValueError(f"{prog['arch']}: the configuration file says "
                             f"{key}={conf[key]!r}, the program runs {have!r}")
    if kind == "qwen2" and not cfg.qkv_bias:
        raise ValueError(f"{prog['arch']}: qwen2 has q/k/v biases")
    return cfg


def engine_spec(method: dict) -> engine.EngineSpec:
    """SAVIC's engine spec, as ``launch/train.py`` resolves its CLI."""
    if method["method"] != "savic":
        raise ValueError(f"method {method['method']!r}: only savic is wired")
    pc = PrecondConfig(kind=method["preconditioner"], alpha=method["alpha"])
    sv = SavicConfig(gamma=method["gamma"], beta1=method["beta1"],
                     scaling=method["scaling"])
    return savic.engine_spec(pc, sv)


def param_shapes(cfg):
    """The program's own parameter tree, as shapes."""
    model = repro_models.build(cfg, repro_models.ModelCallConfig())
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def _placed(shapes, sharding):
    """ShapeDtypeStructs carrying ``sharding``."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), shapes)


class RoundStep:
    """The compiled round step of one cell, and how its inputs get there.

    ``make_params(key)`` is the benchmark's seeded weights of one replica.
    ``init_state(key)`` makes the engine state on the cell's devices in one
    jitted call; ``put(batch)`` places the round's batch; ``step(state,
    batch, r)`` -> (state, metrics), the state donated.
    """

    def __init__(self, conf, mix, method, devices, make_params,
                 dtype=None):
        self.cfg = model_config(conf)
        self.spec = engine_spec(method)
        if len(devices) != 1:
            raise ValueError("the round step runs on one device")
        self.make_params = make_params
        call = repro_models.ModelCallConfig(
            dtype=jnp.dtype(dtype or conf["dtype"]))
        model = repro_models.build(self.cfg, call)
        self._fn = jax.jit(engine.build_round_step(model.loss, self.spec),
                           donate_argnums=0)
        self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
        self._root = jax.random.PRNGKey(0)
        make_state = lambda key: engine.init_state(key, make_params,
                                                   self.spec, mix.clients)
        self._init = jax.jit(make_state, out_shardings=self.sharding)
        self.state_shape = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        self.compiled = None

    def compile(self, batch_shape) -> dict:
        """AOT-compile the step; its compile seconds and the compiler's
        memory figures (per device)."""
        args = (_placed(self.state_shape, self.sharding),
                _placed(batch_shape, self.sharding),
                _placed(jax.eval_shape(lambda: self._root), self.sharding))
        t = time.perf_counter()
        self.compiled = self._fn.lower(*args).compile()
        info = {"compile_s": time.perf_counter() - t}
        ma = self.compiled.memory_analysis()
        if ma is not None:
            info.update(argument_bytes=ma.argument_size_in_bytes,
                        temp_bytes=ma.temp_size_in_bytes,
                        peak_bytes=ma.peak_memory_in_bytes)
        return info

    def init_state(self, key):
        return self._init(key)

    def put(self, batch: dict):
        return jax.device_put(batch, self.sharding)

    def step(self, state, batch, r: int):
        key = jax.device_put(jax.random.fold_in(self._root, r), self.sharding)
        return self.compiled(state, batch, key)

    def first_grad(self, state, read, index):
        """``read(|g_avg|, index)`` of the client-averaged gradient the
        optimizer took in the first round, read back from its state: Adam's
        debiased first D update sets D**2 = g_avg**2."""
        return jax.jit(lambda d, i: read(jax.tree.map(jnp.sqrt, d), i))(
            state["precond"]["d"], index)

    def change(self, state, key, read, index):
        """``read(x - x0, index)`` of the server point (client 0's copy,
        equal to every client's after the sync) from the seeded start."""
        return jax.jit(lambda pm, k, i: read(jax.tree.map(
            lambda a, b: a[0] - b, pm, self.make_params(k)), i))(
            state["params"], key, index)
