"""Procedural Synthetic-NeRF-like scenes + posed views + ray batches.

The container is offline, so the 8 Blender scenes are replaced by analytic
SDF scenes (named after the originals) with a sphere-traced ground-truth
renderer. Scenes are constructed to span a wide occupancy/factor sparsity
range (ficus/mic/materials sparse -> lego/ship dense), which is what the
paper's Fig. 5 / hybrid-encoding experiments need.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.rtnerf import NeRFConfig
from repro.core.rendering import Camera, camera_rays, look_at_camera

SPHERE, BOX, CYL = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class Scene:
    name: str
    prim_type: np.ndarray    # (P,) int
    center: np.ndarray       # (P,3)
    size: np.ndarray         # (P,3) radii / half-extents / (r, h, -)
    color: np.ndarray        # (P,3)


def _mk(name, prims) -> Scene:
    t = np.array([p[0] for p in prims], np.int32)
    c = np.array([p[1] for p in prims], np.float32)
    s = np.array([p[2] for p in prims], np.float32)
    col = np.array([p[3] for p in prims], np.float32)
    return Scene(name, t, c, s, col)


def make_scene(name: str) -> Scene:
    """8 scenes named after Synthetic-NeRF, ordered sparse -> dense."""
    # a stable digest, not hash(): str hashes are salted per process
    seed = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "big")
    rng = np.random.RandomState(seed % (2 ** 31))
    if name == "mic":          # very sparse: thin stand + small head
        return _mk(name, [
            (SPHERE, [0, 0, 0.7], [0.18, 0, 0], [0.8, 0.8, 0.85]),
            (CYL, [0, 0, -0.1], [0.04, 0.75, 0], [0.3, 0.3, 0.32]),
            (BOX, [0, 0, -0.9], [0.3, 0.3, 0.05], [0.2, 0.2, 0.22]),
        ])
    if name == "materials":    # sparse row of spheres
        prims = []
        for i in range(6):
            x = -1.1 + i * 0.44
            prims.append((SPHERE, [x, 0, -0.6], [0.2, 0, 0],
                          [0.2 + 0.13 * i, 0.9 - 0.12 * i, 0.4]))
        return _mk(name, prims)
    if name == "ficus":        # thin trunk + leaf blobs
        prims = [(CYL, [0, 0, -0.4], [0.05, 0.55, 0], [0.45, 0.3, 0.15])]
        for i in range(9):
            a = rng.rand() * 2 * np.pi
            r = 0.25 + 0.45 * rng.rand()
            z = 0.15 + 0.75 * rng.rand()
            prims.append((SPHERE, [r * np.cos(a), r * np.sin(a), z],
                          [0.13, 0, 0], [0.1, 0.5 + 0.3 * rng.rand(), 0.12]))
        return _mk(name, prims)
    if name == "drums":
        return _mk(name, [
            (CYL, [-0.5, 0.3, -0.45], [0.38, 0.22, 0], [0.85, 0.2, 0.2]),
            (CYL, [0.5, 0.3, -0.45], [0.38, 0.22, 0], [0.2, 0.3, 0.85]),
            (CYL, [0, -0.5, -0.35], [0.45, 0.3, 0], [0.9, 0.75, 0.2]),
            (SPHERE, [-0.75, -0.5, 0.3], [0.22, 0, 0], [0.9, 0.85, 0.3]),
            (SPHERE, [0.75, -0.5, 0.3], [0.22, 0, 0], [0.9, 0.85, 0.3]),
        ])
    if name == "chair":
        return _mk(name, [
            (BOX, [0, 0, -0.25], [0.45, 0.45, 0.07], [0.6, 0.35, 0.15]),
            (BOX, [0, 0.42, 0.35], [0.45, 0.06, 0.55], [0.65, 0.4, 0.2]),
            (BOX, [-0.38, -0.38, -0.7], [0.06, 0.06, 0.4], [0.35, 0.2, 0.1]),
            (BOX, [0.38, -0.38, -0.7], [0.06, 0.06, 0.4], [0.35, 0.2, 0.1]),
            (BOX, [-0.38, 0.38, -0.7], [0.06, 0.06, 0.4], [0.35, 0.2, 0.1]),
            (BOX, [0.38, 0.38, -0.7], [0.06, 0.06, 0.4], [0.35, 0.2, 0.1]),
        ])
    if name == "hotdog":
        return _mk(name, [
            (BOX, [0, 0, -0.55], [0.9, 0.55, 0.08], [0.92, 0.92, 0.9]),
            (CYL, [0, -0.12, -0.32], [0.16, 0.65, 1], [0.85, 0.6, 0.3]),
            (CYL, [0, 0.12, -0.32], [0.16, 0.65, 1], [0.85, 0.6, 0.3]),
            (CYL, [0, 0, -0.22], [0.12, 0.6, 1], [0.7, 0.25, 0.1]),
        ])
    if name == "lego":         # dense: grid of bricks
        prims = []
        for i in range(4):
            for j in range(3):
                z = -0.6 + 0.28 * (i % 3)
                prims.append((BOX, [-0.6 + 0.4 * i, -0.4 + 0.4 * j, z],
                              [0.18, 0.18, 0.12],
                              [0.8, 0.65 - 0.1 * j, 0.1 + 0.2 * (i % 2)]))
        prims.append((BOX, [0, 0, -0.85], [0.9, 0.7, 0.06], [0.4, 0.4, 0.42]))
        return _mk(name, prims)
    if name == "ship":         # dense, large extent
        return _mk(name, [
            (BOX, [0, 0, -0.72], [1.2, 1.2, 0.05], [0.25, 0.45, 0.6]),
            (BOX, [0, 0, -0.5], [0.85, 0.3, 0.16], [0.5, 0.33, 0.18]),
            (BOX, [0.5, 0, -0.2], [0.08, 0.08, 0.35], [0.45, 0.3, 0.2]),
            (BOX, [-0.3, 0, -0.1], [0.06, 0.06, 0.45], [0.45, 0.3, 0.2]),
            (BOX, [-0.3, 0, 0.15], [0.02, 0.5, 0.25], [0.95, 0.95, 0.9]),
            (BOX, [0.5, 0, 0.0], [0.02, 0.38, 0.18], [0.95, 0.95, 0.9]),
        ])
    raise KeyError(name)


SCENES = ("chair", "drums", "ficus", "hotdog", "lego", "materials", "mic",
          "ship")


# --------------------------------------------------------------------------
# analytic SDF + ground-truth renderer
# --------------------------------------------------------------------------


def scene_sdf(scene: Scene, p: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """p (N,3) -> (dist (N,), nearest-prim color (N,3))."""
    t = jnp.asarray(scene.prim_type)
    c = jnp.asarray(scene.center)
    s = jnp.asarray(scene.size)
    rel = p[:, None, :] - c[None]                   # (N,P,3)

    d_sphere = jnp.linalg.norm(rel, axis=-1) - s[None, :, 0]
    q = jnp.abs(rel) - s[None]
    d_box = (jnp.linalg.norm(jnp.maximum(q, 0.0), axis=-1)
             + jnp.minimum(jnp.max(q, axis=-1), 0.0))
    dxy = jnp.linalg.norm(rel[..., :2], axis=-1) - s[None, :, 0]
    dz = jnp.abs(rel[..., 2]) - s[None, :, 1]
    qc = jnp.stack([dxy, dz], axis=-1)
    d_cyl = (jnp.linalg.norm(jnp.maximum(qc, 0.0), axis=-1)
             + jnp.minimum(jnp.max(qc, axis=-1), 0.0))

    d = jnp.where(t[None] == SPHERE, d_sphere,
                  jnp.where(t[None] == BOX, d_box, d_cyl))   # (N,P)
    best = jnp.argmin(d, axis=-1)
    col = jnp.asarray(scene.color)[best]
    return jnp.min(d, axis=-1), col


def render_gt(scene: Scene, cam: Camera, *, n_steps: int = 64,
              light=(0.4, 0.3, 0.85)) -> jax.Array:
    """Sphere-traced ground truth image (H*W, 3), white background."""
    o, d = camera_rays(cam)
    t = jnp.full((o.shape[0],), 1.0)

    def step(t, _):
        p = o + d * t[:, None]
        dist, _ = scene_sdf(scene, p)
        return t + jnp.clip(dist, -0.05, 0.3), None

    t, _ = jax.lax.scan(step, t, None, length=n_steps)
    p = o + d * t[:, None]
    dist, col = scene_sdf(scene, p)
    hit = (dist < 5e-3) & (t < 7.0)

    eps = 1e-3
    def grad_axis(i):
        e = jnp.zeros((3,)).at[i].set(eps)
        return (scene_sdf(scene, p + e)[0] - scene_sdf(scene, p - e)[0])
    n = jnp.stack([grad_axis(i) for i in range(3)], axis=-1)
    n = n / jnp.maximum(jnp.linalg.norm(n, axis=-1, keepdims=True), 1e-8)
    l = jnp.asarray(light) / np.linalg.norm(light)
    lam = jnp.clip(jnp.einsum("nd,d->n", n, l), 0.0, 1.0)
    shade = (0.35 + 0.65 * lam)[:, None] * col
    return jnp.where(hit[:, None], shade, 1.0)


def make_cameras(n_views: int, h: int, w: int, radius: float = 4.0,
                 elevation: float = 0.5) -> List[Camera]:
    cams = []
    for i in range(n_views):
        a = 2 * np.pi * i / n_views
        o = np.array([radius * np.cos(a) * np.cos(elevation),
                      radius * np.sin(a) * np.cos(elevation),
                      radius * np.sin(elevation)], np.float32)
        cams.append(look_at_camera(o, [0, 0, 0], 1.2 * w, h, w))
    return cams


@dataclasses.dataclass
class RayDataset:
    rays_o: np.ndarray      # (M,3)
    rays_d: np.ndarray      # (M,3)
    rgb: np.ndarray         # (M,3)

    def batches(self, batch: int, seed: int = 0):
        rng = np.random.RandomState(seed)
        m = self.rays_o.shape[0]
        while True:
            idx = rng.randint(0, m, size=batch)
            yield (jnp.asarray(self.rays_o[idx]), jnp.asarray(self.rays_d[idx]),
                   jnp.asarray(self.rgb[idx]))


def build_dataset(scene: Scene, n_views: int, h: int, w: int) -> RayDataset:
    cams = make_cameras(n_views, h, w)
    render = jax.jit(lambda c2w, orig: render_gt(
        scene, Camera(c2w, orig, cams[0].focal, h, w)))
    ro, rd, rgb = [], [], []
    for cam in cams:
        img = np.asarray(render(cam.c2w, cam.origin))
        o, d = camera_rays(cam)
        ro.append(np.asarray(o))
        rd.append(np.asarray(d))
        rgb.append(img)
    return RayDataset(np.concatenate(ro), np.concatenate(rd),
                      np.concatenate(rgb))
