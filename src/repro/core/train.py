"""NeRF training loop: photometric MSE + L1 sparsity + TV, periodic
occupancy rebuild, compressed-native optimisation with support revival.

API: `NerfTrainer` is the incremental stepper (`step()` / `reencode()` /
`snapshot()` / `final()`) that the online fine-tuning service
(serving/finetune.py) drives one step at a time between `swap_field`
publications; `train_nerf(cfg, scene, steps=...)` runs it to completion
and returns a `TrainResult`; `eval_view` renders one view through either
pipeline for PSNR reporting.

Training renders use the differentiable uniform pipeline (as in TensoRF);
the RT-NeRF pipeline is the inference path it is benchmarked against.

Compressed-native training (ROADMAP "compressed training"): after a dense
warmup, the field is pruned and hybrid-encoded (core/field.py), and every
optimizer step from then on applies gradients to the *encoded* field's nnz
values (`FieldBackend.trainable()` — packed non-zeros + MLP/basis). The
bitmap/COO support is fixed between re-encode boundaries (every
`occ_every` steps the field is re-pruned and re-encoded, so the support
tracks the emerging sparsity). At each boundary the support is also
*revived* (ROADMAP "support revival"): entries pruned to zero before an
earlier encode get no gradient and could otherwise never regrow, so the
top `revive_frac` zero entries by dense-gradient magnitude are re-seeded
(`DenseField.revive`) before the re-prune — RigL-style regrowth at exactly
the cadence the support is re-chosen anyway. Training renders are
occupancy-free (as in TensoRF); the occupancy grid is built once from the
final field, at the one shared cutoff `cfg.occ_sigma_thresh`. The factors
stay encoded between steps — what the trainer holds is what the checkpoint
stores and the serving engine publishes (`swap_field`), with no
encode-at-serve-time step.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import rendering
from repro.core import sparse, tensorf
from repro.data import rays as rays_lib
from repro.optim import Optimizer, adamw


@dataclasses.dataclass
class TrainResult:
    field: field_lib.FieldBackend
    cubes: occ_lib.CubeSet
    history: list


def nerf_loss(field, cfg: NeRFConfig, rays_o, rays_d, target, cubes=None):
    f = field_lib.as_backend(field, cfg)
    rgb, _ = rendering.render_uniform(
        f, cfg, cubes, rays_o, rays_d,
        use_occupancy=cubes is not None)
    mse = jnp.mean(jnp.square(rgb - target))
    loss = mse + cfg.sigma_sparsity_l1 * f.l1() + cfg.tv_weight * f.tv()
    return loss, mse


def field_optimizer(cfg: NeRFConfig) -> Optimizer:
    """Adam at `cfg.lr_grid` for the VM factors and at `cfg.lr_mlp` for the
    basis and color MLP, as TensoRF trains them. At one shared grid rate
    the published config's 128-wide MLP saturates its sigmoid within a few
    steps and the field never leaves the blank-canvas PSNR. Works on the
    trainable dicts of both backends (dense keys, or "factors/..." /
    "extras/..." for encoded fields)."""
    grid = adamw(lr=cfg.lr_grid, b2=0.99)
    mlp = adamw(lr=cfg.lr_mlp, b2=0.99)

    def is_factor(k: str) -> bool:
        return k in sparse.FACTOR_KEYS or k.startswith("factors/")

    def split(t):
        return ({k: v for k, v in t.items() if is_factor(k)},
                {k: v for k, v in t.items() if not is_factor(k)})

    def init(params):
        g, m = split(params)
        return {"grid": grid.init(g), "mlp": mlp.init(m)}

    def update(grads, state, params, _loss=None):
        (gg, gm), (pg, pm) = split(grads), split(params)
        pg, sg = grid.update(gg, state["grid"], pg)
        pm, sm = mlp.update(gm, state["mlp"], pm)
        return {**pg, **pm}, {"grid": sg, "mlp": sm}

    return Optimizer(init, update, "adamw_grid_mlp")


class NerfTrainer:
    """Incremental compressed-native trainer: one optimizer step at a time.

    `train_nerf` drives this to completion; `serving.finetune.FineTuneLoop`
    drives it on a background thread, interleaving `step()` with
    `snapshot()` -> `RenderEngine.swap_field` publications. The trainer can
    start from a fresh init (`field=None`) or resume from any FieldBackend
    — e.g. the field a serving engine is currently rendering from, for
    online fine-tuning of a live scene.

    State: `field` is the structure template (encoded or dense), `_tvals`
    the float payloads the optimizer owns. At every `occ_every` boundary
    `reencode()` revives + re-prunes + re-encodes, rebuilding the optimizer
    state and the jitted step for the new trainable leaf shapes.
    """

    def __init__(self, cfg: NeRFConfig, scene_name: str, *,
                 field: Optional[field_lib.FieldBackend] = None,
                 n_views: int = 12, image_hw: int = 64,
                 occ_every: int = 200, prune_tol: float = 1e-3,
                 revive_frac: float = 0.05,
                 revive_eps: Optional[float] = None,
                 seed: int = 0, compressed: bool = True,
                 verbose: bool = False):
        self.cfg = cfg
        self.scene_name = scene_name
        self.compressed = bool(compressed)
        self.occ_every = int(occ_every)
        self.prune_tol = float(prune_tol)
        self.revive_frac = float(revive_frac)
        # revived entries must clear the next tol-prune or revival is a no-op
        self.revive_eps = (2.0 * self.prune_tol if revive_eps is None
                           else float(revive_eps))
        self.verbose = bool(verbose)
        scene = rays_lib.make_scene(scene_name)
        ds = rays_lib.build_dataset(scene, n_views, image_hw, image_hw)
        self._it = ds.batches(cfg.train_rays, seed=seed)
        # revival grads come from their own stream so enabling revival
        # doesn't shift which rays the optimizer steps see
        self._revive_it = ds.batches(cfg.train_rays, seed=seed + 1)
        if field is None:
            field = field_lib.DenseField(
                tensorf.init_field(cfg, jax.random.PRNGKey(seed)), cfg)
        self.opt = field_optimizer(cfg)
        self._dense_grad = jax.jit(lambda params, ro, rd, tgt: jax.grad(
            lambda p: nerf_loss(field_lib.DenseField(p, cfg), cfg,
                                ro, rd, tgt)[0])(params))
        self.step_count = 0
        self._rebind(field_lib.as_backend(field, cfg))

    def _rebind(self, field: field_lib.FieldBackend):
        """Adopt `field` as the new structure template: fresh optimizer
        state + a jitted step over its trainable leaves. The encoded
        structure (bitmap words / rowptr / COO coords) rides in the step's
        closure; only the float payloads flow through grad/update."""
        cfg, opt = self.cfg, self.opt

        @jax.jit
        def step_fn(tvals, opt_state, ro, rd, tgt):
            def loss_fn(v):
                return nerf_loss(field.with_trainable(v), cfg, ro, rd, tgt)
            (loss, mse), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(tvals)
            tvals2, opt_state2 = opt.update(grads, opt_state, tvals)
            return tvals2, opt_state2, loss, mse

        self.field = field
        self._tvals = field.trainable()
        self._opt_state = opt.init(self._tvals)
        self._step_fn = step_fn

    def reencode(self):
        """Re-encode boundary: revive the support from dense gradients,
        re-prune, hybrid-encode, and rebuild the optimizer + jitted step
        (the trainable leaf shapes change with the support)."""
        field = self.field.with_trainable(self._tvals)
        dense = field.decode()
        if self.revive_frac > 0.0:
            ro, rd, tgt = next(self._revive_it)
            grads = self._dense_grad(dense.params, ro, rd, tgt)
            dense = dense.revive(grads, frac=self.revive_frac,
                                 eps=self.revive_eps)
        self._rebind(dense.prune(tol=self.prune_tol).encode())
        if self.verbose:
            print(f"  [{self.scene_name}] step {self.step_count:5d} "
                  f"re-encoded field "
                  f"({self.field.compression_ratio():.2f}x factor bytes)",
                  flush=True)

    def step(self) -> Dict[str, float]:
        """One optimizer step (re-encoding first at `occ_every`
        boundaries); returns {step, loss, psnr} for this batch."""
        i = self.step_count
        if self.compressed and i > 0 and i % self.occ_every == 0:
            self.reencode()
        ro, rd, tgt = next(self._it)
        self._tvals, self._opt_state, loss, mse = self._step_fn(
            self._tvals, self._opt_state, ro, rd, tgt)
        self.step_count = i + 1
        p = float(-10 * np.log10(max(float(mse), 1e-10)))
        return {"step": i, "loss": float(loss), "psnr": p}

    def snapshot(self) -> field_lib.FieldBackend:
        """The current field with the optimizer's payloads applied — what a
        publication (`swap_field`) or checkpoint should see. Cheap: no
        decode, no re-encode."""
        return self.field.with_trainable(self._tvals)

    def final(self) -> TrainResult:
        """Finish: prune, encode (compressed mode), build the occupancy
        cube set at `cfg.occ_sigma_thresh`."""
        field = self.snapshot().prune(tol=self.prune_tol)
        if self.compressed:
            field = field.encode()
        occ = occ_lib.build_occupancy(field, self.cfg)
        cubes = occ_lib.extract_cubes(occ, self.cfg)
        return TrainResult(field=field, cubes=cubes, history=[])


def train_nerf(cfg: NeRFConfig, scene_name: str, *, steps: int = 400,
               n_views: int = 12, image_hw: int = 64,
               occ_every: int = 200, prune_tol: float = 1e-3,
               revive_frac: float = 0.05,
               seed: int = 0, log_every: int = 100, verbose: bool = True,
               compressed: bool = True) -> TrainResult:
    """Train a TensoRF field; return the final (encoded) FieldBackend +
    occupancy cubes.

    compressed=True (default): at every `occ_every` boundary the field is
    pruned (`prune_tol`), hybrid-encoded — with the support revived first
    (`revive_frac`, see NerfTrainer/DenseField.revive) — and the optimizer
    continues on the encoded representation's nnz values; the field is
    never densified again. compressed=False keeps the legacy dense loop end
    to end (the baseline the compressed-parity test measures against). The
    occupancy grid is built once, from the final field, at
    `cfg.occ_sigma_thresh` (training renders don't consume occupancy).
    """
    trainer = NerfTrainer(cfg, scene_name, n_views=n_views,
                          image_hw=image_hw, occ_every=occ_every,
                          prune_tol=prune_tol, revive_frac=revive_frac,
                          seed=seed, compressed=compressed, verbose=verbose)
    history = []
    for i in range(steps):
        rec = trainer.step()
        if i % log_every == 0 or i == steps - 1:
            history.append(rec)
            if verbose:
                print(f"  [{scene_name}] step {i:5d} "
                      f"loss {rec['loss']:.5f} "
                      f"train-psnr {rec['psnr']:.2f}", flush=True)
    res = trainer.final()
    return TrainResult(field=res.field, cubes=res.cubes, history=history)


def eval_view(field, cfg: NeRFConfig, cubes, cam, gt, *,
              pipeline: str = "rtnerf", order_mode: str = "octant",
              chunk: int = 1, intersect: str = "box"):
    """Render one view with either pipeline; return (psnr, stats, img).

    `field` is anything `field.as_backend` accepts; an encoded field is
    sampled from its hybrid bitmap/COO streams on BOTH pipelines (the
    uniform baseline no longer needs a decompressed copy).
    """
    from repro.core import pipeline as rt_pipe

    f = field_lib.as_backend(field, cfg)
    if pipeline == "rtnerf":
        img, stats = rt_pipe.render_rtnerf(f, cfg, cubes, cam,
                                           order_mode=order_mode, chunk=chunk,
                                           intersect=intersect)
    else:
        o, d = rendering.camera_rays(cam)
        img, stats = rendering.render_uniform(f, cfg, cubes, o, d)
    p = float(rendering.psnr(jnp.clip(img, 0, 1), gt))
    return p, {k: float(v) for k, v in stats.items()}, img
