"""Logical->mesh resolution rules + multi-device subprocess tests (8 virtual
devices; spawned so the main test process keeps 1 device)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.models.sharding import (AxisRules, DEFAULT_ACT_RULES,
                                   DEFAULT_PARAM_RULES, make_rules,
                                   resolve_spec)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def rules16():
    ar = AxisRules(mesh=FakeMesh({"data": 16, "model": 16}),
                   param_rules=dict(DEFAULT_PARAM_RULES),
                   act_rules=dict(DEFAULT_ACT_RULES))
    return ar


def test_divisibility_drop():
    ar = rules16()
    # 40 heads % 16 != 0 -> dropped (qwen1.5)
    spec = resolve_spec((5120, 40, 128), ("embed", "heads", "head_dim"),
                        ar.param_rules, ar)
    assert spec == P("data", None, None)
    # 48 heads ok
    spec = resolve_spec((6144, 48, 128), ("embed", "heads", "head_dim"),
                        ar.param_rules, ar)
    assert spec == P("data", "model", None)


def test_axis_reuse_conflict():
    ar = rules16()
    # experts takes model; mlp then can't reuse it
    spec = resolve_spec((256, 7168, 2048), ("experts", "embed", "mlp"),
                        ar.param_rules, ar)
    assert spec == P("model", "data", None)
    # grok: 8 experts don't divide -> mlp picks model instead
    spec = resolve_spec((8, 6144, 32768), ("experts", "embed", "mlp"),
                        ar.param_rules, ar)
    assert spec == P(None, "data", "model")


def test_vocab_padding_shards():
    from repro.configs.registry import ARCHS
    ar = rules16()
    for cfg in ARCHS.values():
        assert cfg.vocab_padded % 16 == 0
        spec = resolve_spec((cfg.vocab_padded, cfg.d_model),
                            ("vocab", "embed"), ar.param_rules, ar)
        assert spec == P("model", "data"), cfg.name


def test_heads_shardable_rules():
    from repro.configs.registry import ARCHS
    from repro.models.attention import heads_shardable
    assert heads_shardable(ARCHS["deepseek-v3-671b"])       # 128 H MLA
    assert heads_shardable(ARCHS["granite-34b"])            # MQA via G=48
    assert heads_shardable(ARCHS["seamless-m4t-large-v2"])  # kv=16
    assert not heads_shardable(ARCHS["qwen1.5-32b"])        # 40 heads
    assert not heads_shardable(ARCHS["grok-1-314b"])        # kv=8, G=6
    assert not heads_shardable(ARCHS["llama3.2-1b"])        # kv=8, G=4


SUBPROCESS_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
"""


def run_sub(body: str):
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = SUBPROCESS_PRELUDE.format(src=os.path.abspath(src)) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_multidevice_train_step_matches_single():
    """(2 data x 2 model) sharded train loss == single-device loss."""
    out = run_sub("""
    from repro.configs.registry import ARCHS, reduced
    from repro.models import transformer as tf
    from repro.models.common import split_pl
    from repro.models.sharding import make_rules, param_sharding, use_rules
    from repro.launch.steps import batch_sharding
    from repro.configs.base import ShapeConfig
    from repro.data.tokens import TokenStream

    cfg = reduced(ARCHS["llama3.2-1b"])
    shape = ShapeConfig("t", 16, 4, "train")
    params, logical = split_pl(tf.init_model(cfg, jax.random.PRNGKey(0)))
    batch = TokenStream(cfg, shape).batch(0)

    loss1, _ = jax.jit(lambda p, b: tf.model_loss(p, cfg, b))(params, batch)

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = make_rules(mesh)
    p_sh = param_sharding(params, logical, rules)
    _, b_sh = batch_sharding(cfg, shape, rules)
    pp = jax.device_put(params, p_sh)
    bb = jax.device_put(batch, b_sh)

    def f(p, b):
        with use_rules(rules):
            return tf.model_loss(p, cfg, b)
    loss2, _ = jax.jit(f, in_shardings=(p_sh, b_sh))(pp, bb)
    print("L1", float(loss1), "L2", float(loss2))
    assert abs(float(loss1) - float(loss2)) < 5e-2, (loss1, loss2)
    """)
    assert "L1" in out


def test_gpipe_matches_reference():
    out = run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.launch.pipeline import gpipe, mlp_stage, reference_apply

    mesh = jax.make_mesh((4, 2), ("stage", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    L, D, F = 8, 16, 32
    key = jax.random.PRNGKey(0)
    params = {
        "w1": jax.random.normal(key, (L, D, F)) * 0.1,
        "w2": jax.random.normal(jax.random.fold_in(key, 1), (L, F, D)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (6, 4, D))  # 6 micro
    pp = gpipe(mlp_stage, mesh)
    with mesh:
        y = jax.jit(pp)(params, x)
    y_ref = reference_apply(params, x)
    err = float(jnp.abs(y - y_ref).max())
    print("pipeline err", err)
    assert err < 1e-4
    """)
    assert "pipeline err" in out


def test_elastic_remesh_8_to_4_devices():
    out = run_sub("""
    from repro.launch.elastic import make_mesh_from
    devs = jax.devices()
    m8 = make_mesh_from(devs, model_axis=2)
    assert dict(m8.shape) == {"data": 4, "model": 2}
    m4 = make_mesh_from(devs[:4], model_axis=2)
    assert dict(m4.shape) == {"data": 2, "model": 2}
    m3 = make_mesh_from(devs[:3], model_axis=2)   # odd survivor count
    assert dict(m3.shape) == {"data": 3, "model": 1}
    print("remesh ok")
    """)
    assert "remesh ok" in out


def test_nerf_train_cell_lowers_with_trainer_optimizer():
    """The dry-run train cell steps the field with the trainer's optimizer
    (split grid/MLP Adam), so its lowering sizes the step that trains."""
    import jax.numpy as jnp

    from repro.configs.rtnerf import NeRFShape, demo_config
    from repro.core import tensorf
    from repro.core.distributed import lower_nerf_cell
    from repro.core.train import field_optimizer

    cfg = demo_config(tiny=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    lowered, _ = lower_nerf_cell(cfg, NeRFShape("t", 256, "train"), mesh)
    params = jax.eval_shape(lambda k: tensorf.init_field(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    want = jax.eval_shape(field_optimizer(cfg).init, params)
    (_, state, _), _ = lowered.args_info
    assert jax.tree.structure(state) == jax.tree.structure(want)
    lowered.compile()
