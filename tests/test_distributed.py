"""Sharding rules + single-device mesh integration (the 512-device path is
exercised by launch.dryrun; here we verify rule correctness and that the
sharded step functions run on the smoke mesh)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.api import (activation_policy, policy_from_mesh)
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        param_spec, params_shardings)
from repro.launch.mesh import make_smoke_mesh
from repro.launch.steps import (input_specs, make_opt_config, model_shapes,
                                opt_shapes, serve_step, train_step)
from repro.configs.base import SHAPES
from repro.models.model import init_cache, init_model
from repro.optim.adamw import init_opt_state


def fake_mesh_16x16() -> Mesh:
    """Axis-shape bookkeeping only — never touches devices (we build the
    mesh from a reshaped view of the single CPU device repeated? No: we use
    an abstract mesh substitute)."""
    # AbstractMesh carries axis names/sizes without devices.
    from jax.sharding import AbstractMesh
    return AbstractMesh((("data", 16), ("model", 16)))


def test_param_spec_rules():
    mesh = fake_mesh_16x16()
    # embedding: vocab divisible → (model, data)
    assert param_spec("embed/table", (32000, 2560), mesh) == \
        P("model", "data")
    # odd vocab → fall back to d on model
    assert param_spec("embed/table", (50280, 1024), mesh) == \
        P(None, "model")
    # generic projection: out on model, in on data
    assert param_spec("layers/attn/wq/w", (48, 5120, 5120), mesh) == \
        P(None, "data", "model")
    # expert-stacked: E on model, d on data
    assert param_spec("layers/moe/w_gate", (8, 160, 64, 128), mesh) == \
        P(None, "model", "data", None)
    # small norm scale stays replicated
    assert param_spec("ln_f/scale", (64,), mesh) == P(None)


def test_param_spec_divisibility_fallback():
    mesh = fake_mesh_16x16()
    # out dim 33 not divisible by 16 → TP lands on the in dim instead
    spec = param_spec("x/w", (64, 33), mesh)
    assert spec == P("model", None) or spec == P("data", None) \
        or spec[-1] is None


def test_batch_and_cache_shardings_divisibility():
    mesh = fake_mesh_16x16()
    cfg = get_config("qwen2.5-14b")
    specs = input_specs(cfg, SHAPES["decode_32k"])
    tok_sh = batch_shardings({"tokens": specs["tokens"]}, mesh)["tokens"]
    assert tok_sh.spec[0] in ("data", ("data",))  # 128 % 16 == 0
    c_sh = cache_shardings(specs["cache"], mesh)
    leaves = jax.tree.leaves(c_sh)
    assert any(s.spec != P() for s in leaves)     # something is sharded
    # long_500k: batch 1 → batch unsharded everywhere
    cfg2 = get_config("mamba2-370m")
    specs2 = input_specs(cfg2, SHAPES["long_500k"])
    tok2 = batch_shardings({"tokens": specs2["tokens"]}, mesh)["tokens"]
    assert tok2.spec == P(None, None) or tok2.spec == P()


def test_train_and_serve_steps_run_on_smoke_mesh():
    mesh = make_smoke_mesh()
    cfg = get_config("zamba2-2.7b", reduced=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    opt_cfg = make_opt_config(cfg)
    opt_state = init_opt_state(params, opt_cfg)
    p_sh = params_shardings(params, mesh)
    params = jax.device_put(params, p_sh)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32)}
    with mesh, activation_policy(policy_from_mesh(mesh)):
        step = jax.jit(functools.partial(train_step, cfg=cfg,
                                         opt_cfg=opt_cfg, microbatches=2))
        params2, opt2, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))

    cache = init_cache(cfg, 2, 16)
    with mesh:
        logits, cache2 = jax.jit(
            functools.partial(serve_step, cfg=cfg))(
                params2, jnp.zeros((2, 1), jnp.int32), cache,
                jnp.int32(0))
    assert np.isfinite(np.asarray(logits)).all()


def test_input_specs_cover_all_cells():
    from repro.configs import ARCH_NAMES, shapes_for
    for name in ARCH_NAMES:
        cfg = get_config(name)
        for shp in shapes_for(cfg):
            specs = input_specs(cfg, shp)
            leaves = jax.tree.leaves(specs)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            if shp.kind != "decode":
                total = specs["tokens"].shape[1] + (
                    cfg.frontend_tokens if cfg.frontend != "none" else 0)
                assert total == shp.seq_len
