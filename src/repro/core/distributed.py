"""Distributed NeRF: the paper's workload on the production meshes.

Sharding plan (DESIGN.md §7):
  * rays/pixels over the batch axes ("pod","data") — rendering is ray-
    parallel; each frame request fans out over the data axes,
  * VM component channels R over "model" — Eq. 2 is a sum over R, so each
    model shard evaluates its component slice and GSPMD inserts one tiny
    all-reduce of the (N,) partials,
  * the MLP + occupancy grid replicated (KBs).

Training uses the differentiable uniform pipeline (as TensoRF does); the
cube-centric RT-NeRF pipeline is the serving path — cube-chunk-parallel
across the data axes with the same commutative-transmittance argument as
`chunk>1` (core/pipeline.py docstring).

`lower_nerf_cell` mirrors launch/steps.lower_cell so launch/dryrun.py can
prove the rtnerf x {train_rays, render_800} x {pod, multipod} cells compile.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.rtnerf import NERF_SHAPES, NeRFConfig, NeRFShape
from repro.core import rendering, tensorf
from repro.core.train import field_optimizer
from repro.models.sharding import AxisRules, make_rules


def nerf_param_sharding(cfg: NeRFConfig, params, rules: AxisRules):
    """R-channel (component) sharding for planes/lines; rest replicated."""
    mesh = rules.mesh

    def spec_for(name, arr):
        if "planes" in name or "lines" in name:
            r = arr.shape[1]
            m = mesh.shape.get("model", 1)
            if m > 1 and r % m == 0:
                return NamedSharding(mesh, P(None, "model"))
        return NamedSharding(mesh, P())

    return {k: spec_for(k, v) for k, v in params.items()}


def ray_batch_size(rules: AxisRules) -> int:
    """Devices a ray batch is split over (the mesh's batch axes)."""
    size = 1
    for a in ("pod", "data"):
        size *= rules.mesh.shape.get(a, 1)
    return size


def ray_sharding(rules: AxisRules, n_rays: int):
    """Rays split over the batch axes. A batch that does not divide them is
    an error: replicating it would make every device render every ray."""
    mesh = rules.mesh
    batch_axes = [a for a in ("pod", "data") if a in mesh.shape]
    size = ray_batch_size(rules)
    if size == 1:
        return NamedSharding(mesh, P())
    if n_rays % size:
        raise ValueError(f"{n_rays} rays do not divide over the mesh's "
                         f"{size} batch devices")
    return NamedSharding(
        mesh, P(tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]))


def stream_sharding(rules: AxisRules) -> NamedSharding:
    """Sharding for the resident field's encoded streams (bitmap words /
    rowptr / values, COO coords / values, dense factors, MLP): replicated.
    Every device walks the whole stream — the streams are KB-to-MB scale and
    read-only, while rays are the hot, shardable axis (`ray_sharding`)."""
    return NamedSharding(rules.mesh, P())


def place_field(field, rules: AxisRules):
    """device_put a resident serving field onto the mesh: every stream array
    replicated (stream_sharding). Any FieldBackend (or params dict) is a
    registered pytree, so this is one placement call over the whole tree —
    encoded bitmap/COO streams, integer metadata and MLP alike; on a
    single-device mesh it is a plain device placement (the serving engine's
    fallback path)."""
    return jax.device_put(field, stream_sharding(rules))


def shard_rays(rules: AxisRules, rays_o, rays_d):
    """Place one micro-batched ray chunk across the mesh's batch axes
    (`ray_sharding`: a chunk that does not divide them raises)."""
    sh = ray_sharding(rules, rays_o.shape[0])
    return jax.device_put(rays_o, sh), jax.device_put(rays_d, sh)


def build_render_step(cfg: NeRFConfig):
    """Batched novel-view rendering: rays -> rgb (uniform pipeline with a
    replicated occupancy grid; the serving analogue of Step 2-1/2-2/3)."""

    def render_step(params, occ, rays_o, rays_d):
        from repro.core.occupancy import CubeSet
        cubes = CubeSet(centers=jnp.zeros((1, 3)), valid=jnp.ones((1,), bool),
                        count=1, radius=0.0, occ=occ)
        rgb, _ = rendering.render_uniform(params, cfg, cubes, rays_o, rays_d)
        return rgb

    return render_step


def build_nerf_train_step(cfg: NeRFConfig, opt):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            rgb, _ = rendering.render_uniform(p, cfg, None, batch["rays_o"],
                                              batch["rays_d"],
                                              use_occupancy=False)
            mse = jnp.mean(jnp.square(rgb - batch["rgb"]))
            return mse + cfg.sigma_sparsity_l1 * tensorf.field_l1(p) \
                + cfg.tv_weight * tensorf.field_tv(p)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_step


def nerf_input_specs(cfg: NeRFConfig, shape: NeRFShape):
    n = shape.n_rays
    specs = {
        "rays_o": jax.ShapeDtypeStruct((n, 3), jnp.float32),
        "rays_d": jax.ShapeDtypeStruct((n, 3), jnp.float32),
    }
    if shape.kind == "train":
        specs["rgb"] = jax.ShapeDtypeStruct((n, 3), jnp.float32)
    return specs


def lower_nerf_cell(cfg: NeRFConfig, shape: NeRFShape, mesh):
    """AOT-lower the rtnerf cell on a production mesh (dry-run entry)."""
    rules = make_rules(mesh)
    params_sds = jax.eval_shape(lambda k: tensorf.init_field(cfg, k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_sh = nerf_param_sharding(cfg, params_sds, rules)
    in_specs = nerf_input_specs(cfg, shape)
    r_sh = ray_sharding(rules, shape.n_rays)
    repl = NamedSharding(mesh, P())
    info = {"n_params": sum(int(x.size) for x in jax.tree.leaves(params_sds)),
            "n_active": cfg.param_count()}

    if shape.kind == "train":
        opt = field_optimizer(cfg)
        state_sds = jax.eval_shape(opt.init, params_sds)
        # each Adam group's moments shard like the params they track
        s_sh = {g: {"step": repl,
                    "m": {k: p_sh[k] for k in st["m"]},
                    "v": {k: p_sh[k] for k in st["v"]}}
                for g, st in state_sds.items()}
        fn = build_nerf_train_step(cfg, opt)
        jfn = jax.jit(fn,
                      in_shardings=(p_sh, s_sh,
                                    {k: r_sh for k in in_specs}),
                      out_shardings=(p_sh, s_sh, None),
                      donate_argnums=(0, 1))
        lowered = jfn.lower(params_sds, state_sds, in_specs)
        return lowered, info

    occ_sds = jax.ShapeDtypeStruct((cfg.occ_res,) * 3, jnp.bool_)
    fn = build_render_step(cfg)
    jfn = jax.jit(fn, in_shardings=(p_sh, repl, r_sh, r_sh))
    lowered = jfn.lower(params_sds, occ_sds,
                        in_specs["rays_o"], in_specs["rays_d"])
    return lowered, info
