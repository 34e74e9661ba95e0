"""Subprocess worker for tests/test_sync_kernel.py: the round step of the
reduced mamba2-1.3b (whose in/out projections and embedding tile to
(8, 128)) lowered on a 2x2 mesh of host devices through
``steps.build_train_step``, and on one device as ``--mesh none`` builds it.
Prints ``RESULT {json}``: whether each lowered text holds the one-pass sync
kernel. Needs its own XLA device count, fixed at jax's first init."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import json

import jax

from repro.configs import ShapeConfig
from repro.core import engine
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.models import ModelCallConfig, build


def main():
    arch = "mamba2-1.3b"
    shape = ShapeConfig("train_sync", 32, 4, "train")
    mesh = make_debug_mesh((2, 2))
    built = steps.build_train_step(arch, shape, mesh, reduced=True,
                                   h_local=2)
    with mesh:
        meshed = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate).lower(*built.args)
    spec = built.meta["engine_spec"]
    model = build(built.meta["cfg"], ModelCallConfig())
    state_shape, batch_shape = built.args
    one = jax.jit(engine.build_round_step(model.loss, spec),
                  donate_argnums=0).lower(state_shape, batch_shape,
                                          jax.random.PRNGKey(0))
    params_one = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    print("RESULT " + json.dumps({
        "clients": built.meta["clients"],
        "mesh_kernel": "sync_average" in meshed.as_text(),
        "one_device_kernel": "sync_average" in one.as_text(),
        "plan_mesh": engine.sync_plan(params_one, spec, mesh),
        "plan_one": engine.sync_plan(params_one, spec),
    }), flush=True)


if __name__ == "__main__":
    main()
