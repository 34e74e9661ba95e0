"""The system under test, as the benchmark drives it: the jitted round step
that ``repro.launch.train`` runs, built from a configuration file, a
traffic mix and a cell's method.

* On one chip, as ``--mesh none`` runs it:
  ``repro.core.engine.build_round_step(model.loss, spec)`` under
  ``jax.jit`` with the state donated and a per-round key.
* On several chips, as ``--mesh debug --mesh-shape <n>x1`` runs it: the
  launch layer's ``steps.build_train_step(..., mode="paper")`` on a
  (data n, model 1) mesh of the cell's chips, one client a chip, jitted
  with that step's shardings and the state donated; the step folds its
  key from the state's round counter.

What the program needs to know of a model type is the type's
``model_types/<model_type>.py`` (``spec.model_type``). This is the only
module of the benchmark that imports ``repro`` (besides ``faults.py``,
which only the check's tests and readings use).
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

from benchmarks.chip import spec as bench_spec
from repro import models as repro_models
from repro.configs import ShapeConfig, get_config
from repro.core import PrecondConfig, SavicConfig, engine, savic
from repro.launch import steps


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture cut to the file's depth, with the numbers its type
    ``SET``s. Every width the file states must be the program's, or this
    raises."""
    kind = bench_spec.model_type(conf["model_type"])
    prog = conf["program"]
    cfg = get_config(prog["arch"], reduced=prog["reduced"]).replace(
        n_layers=conf[kind.DEPTH],
        **{field: conf[key] for key, field in kind.SET.items()})
    for key, field in kind.FIELDS.items():
        have = cfg
        for part in field.split("."):
            have = getattr(have, part)
        if have != conf[key]:
            raise ValueError(f"{prog['arch']}: the configuration file says "
                             f"{key}={conf[key]!r}, the program runs {have!r}")
    if hasattr(kind, "check"):
        kind.check(cfg)
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


@contextlib.contextmanager
def _registered_as(cfg):
    """``build_train_step`` looks its architecture up by name; hand it the
    configuration file's ``cfg`` (the registered one with the file's
    ``SET`` numbers) in its place."""
    lookup = steps.get_config
    steps.get_config = lambda arch, reduced=False: cfg
    try:
        yield
    finally:
        steps.get_config = lookup


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
        self.make_params = make_params
        call = repro_models.ModelCallConfig(
            dtype=jnp.dtype(dtype or conf["dtype"]))
        self._root = jax.random.PRNGKey(0)
        if len(devices) == 1:
            self.mesh = None
            model = repro_models.build(self.cfg, call)
            self._fn = jax.jit(engine.build_round_step(model.loss, self.spec),
                               donate_argnums=0)
            self.state_sharding = self.batch_sharding = \
                jax.sharding.SingleDeviceSharding(devices[0])
        else:
            self._build_on_mesh(conf["program"], mix, devices, call)
        make_state = lambda key: engine.init_state(key, make_params,
                                                   self.spec, mix.clients)
        self._init = jax.jit(make_state, out_shardings=self.state_sharding)
        self.state_shape = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        self.compiled = None

    def _build_on_mesh(self, prog, mix, devices, call):
        n = len(devices)
        if mix.clients != n:
            raise ValueError(f"on {n} chips the round step runs one client "
                             f"a chip; the mix has {mix.clients}")
        # as launch/mesh.make_debug_mesh((n, 1)) makes it, on these devices
        self.mesh = jax.make_mesh(
            (n, 1), ("data", "model"), devices=list(devices),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig(f"bench_s{mix.seq_len}", mix.seq_len,
                            n * mix.batch, "train")
        with _registered_as(self.cfg):
            built = steps.build_train_step(
                prog["arch"], shape, self.mesh, mode="paper",
                engine_spec=self.spec, call=call, reduced=prog["reduced"],
                h_local=mix.local_steps, n_layers=self.cfg.n_layers)
        if built.meta["cfg"] != self.cfg:
            raise ValueError(f"the mesh step was built for "
                             f"{built.meta['cfg']}, not the file's {self.cfg}")
        if set(built.args[1]) != {"tokens", "labels"}:
            raise ValueError(f"the mesh step takes a batch of "
                             f"{sorted(built.args[1])}")
        self.spec = built.meta["engine_spec"]
        self._fn = jax.jit(built.fn, in_shardings=built.in_shardings,
                           out_shardings=built.out_shardings,
                           donate_argnums=built.donate)
        self.state_sharding, self.batch_sharding = built.in_shardings

    def compile(self, batch_shape) -> dict:
        """AOT-compile the step; its compile seconds and the compiler's
        memory figures (per device)."""
        t = time.perf_counter()
        if self.mesh is None:
            args = (_placed(self.state_shape, self.state_sharding),
                    _placed(batch_shape, self.batch_sharding),
                    _placed(jax.eval_shape(lambda: self._root),
                            self.batch_sharding))
            self.compiled = self._fn.lower(*args).compile()
        else:
            with self.mesh:
                self.compiled = self._fn.lower(self.state_shape,
                                               batch_shape).compile()
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
        return jax.device_put(batch, self.batch_sharding)

    def step(self, state, batch, r: int):
        if self.mesh is not None:
            return self.compiled(state, batch)
        key = jax.device_put(jax.random.fold_in(self._root, r),
                             self.batch_sharding)
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
