"""Compile-only checks for one chip of a described TPU v5e (2x2 host), at
the published field widths (`configs/rtnerf.CONFIG`). Nothing runs: the
TPU compiler lowers each step for a chip that is described, not attached,
and refuses what the chip would refuse (kernels it cannot lower, programs
that do not fit its 16 GB of HBM).

The topology is described inside a module fixture, never at import, and
every test compiles in this process: only one process at a time may load
the TPU library. Keep all such tests in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.rtnerf import CONFIG as CFG
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import tensorf
from repro.core import train as nerf_train
from repro.serving import RenderEngine

V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def fields():
    """A CONFIG-width field from a seed, pruned to 0.9 and hybrid-encoded
    (the shapes chip_smoke.py serves), and its dense twin."""
    dense = field_lib.DenseField(
        tensorf.init_field(CFG, jax.random.PRNGKey(0)), CFG
    ).prune(sparsity=0.9)
    return {"hybrid": dense.encode(), "dense": dense}


@pytest.fixture
def on_tpu(monkeypatch):
    """Trace as the chip would: code that asks for the backend sees tpu."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    assert used < V5E_HBM, f"{used / 2**30:.2f} GiB does not fit a v5e"


@pytest.mark.parametrize("kind", ["hybrid", "dense"])
def test_engine_render_step_compiles(kind, fields, one_chip,
                                     no_compile_cache, on_tpu):
    """The engine's jitted ray-render step at its default ray_chunk and
    pair budget, for the field as the engine serves it on TPU."""
    field = fields[kind]
    cubes = occ_lib.CubeSet(
        jnp.zeros((CFG.max_cubes, 3)), jnp.zeros((CFG.max_cubes,), bool),
        0, CFG.cube_ball_radius(),
        jnp.zeros((CFG.occ_res,) * 3, bool))
    engine = RenderEngine(CFG, field, cubes, encode=kind == "hybrid")
    assert engine.field.dispatch_path() == (
        "fused_ref" if kind == "hybrid" else "dense")
    rays = jax.ShapeDtypeStruct((engine.ray_chunk, 3), jnp.float32,
                                sharding=one_chip)
    compiled = engine._render.lower(
        _shapes(engine.field, one_chip),
        jax.ShapeDtypeStruct((CFG.max_cubes, 3), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((CFG.max_cubes,), jnp.bool_, sharding=one_chip),
        rays, rays).compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas kernel


def test_trainer_step_compiles(fields, one_chip, no_compile_cache, on_tpu):
    """The dense trainer step at CONFIG's 4096-ray batch (the steps before
    the first re-encode)."""
    trainer = nerf_train.NerfTrainer(CFG, "lego", n_views=1, image_hw=8)
    rays = jax.ShapeDtypeStruct((CFG.train_rays, 3), jnp.float32,
                                sharding=one_chip)
    compiled = trainer._step_fn.lower(
        _shapes(trainer._tvals, one_chip),
        _shapes(trainer._opt_state, one_chip), rays, rays, rays).compile()
    _fits(compiled)


def test_fused_kernel_refused_on_v5e(fields, one_chip, no_compile_cache):
    """The fused Pallas decode-sample kernel does not lower for v5e, which
    is why dispatch serves its jnp twin. When this starts failing the
    kernel lowers: check it against the twin on the chip and revisit
    `ops.fused_mode`."""
    from repro.kernels import fused_sample

    spec, streams = tensorf.fused_field_inputs(fields["hybrid"])
    n = 1024

    def step(streams, basis, pts, base, cid):
        return fused_sample.fused_sigma_app(
            spec, streams, basis, pts, base, cid, grid_res=CFG.grid_res,
            scene_bound=CFG.scene_bound, window=tensorf.fused_window(CFG),
            app_dim=CFG.app_dim, interpret=False)

    with pytest.raises(ValueError, match="Shape mismatch"):
        jax.jit(step).lower(
            _shapes(streams, one_chip),
            _shapes(fields["hybrid"].extras["basis"], one_chip),
            jax.ShapeDtypeStruct((n, 3), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((8, 3), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        ).compile()
