"""Ray-cube geometry of the RT-NeRF pipeline, in plain numpy float32.

The reference renderer and the count of required samples both start from
these hits: every (ray, occupied cube) pair whose line-slab segment ends
beyond `near`, with the cubes in the view's front-to-back octant order and
grouped `cube_chunk` at a time as the renderer scans them.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def look_at(origin, target):
    """(3,3) float32 camera-to-world rotation: columns right, up, back
    (camera looks down -z), world up +z."""
    o = np.asarray(origin, np.float64)
    fwd = np.asarray(target, np.float64) - o
    fwd /= np.linalg.norm(fwd)
    right = np.cross(fwd, [0.0, 0.0, 1.0])
    right /= max(np.linalg.norm(right), 1e-8)
    up = np.cross(right, fwd)
    return np.stack([right, up, -fwd], axis=1).astype(np.float32)


def pixel_rays(c2w, origin, focal: float, h: int, w: int):
    """(h*w, 3) origins and unit directions, row-major, through pixel
    centres."""
    py, px = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    x = (px.reshape(-1) + np.float32(0.5) - np.float32(w / 2.0)) \
        / np.float32(focal)
    y = -(py.reshape(-1) + np.float32(0.5) - np.float32(h / 2.0)) \
        / np.float32(focal)
    d = np.stack([x, y, -np.ones_like(x)], axis=-1) @ np.asarray(
        c2w, np.float32).T
    d = d / np.linalg.norm(d, axis=-1, keepdims=True)
    o = np.broadcast_to(np.asarray(origin, np.float32), d.shape)
    return np.ascontiguousarray(o), d.astype(np.float32)


def octant_order(centers: np.ndarray, origin) -> np.ndarray:
    """Front-to-back cube order of RT-NeRF Sec. 3.2: the 8 octants of the
    scene ranked by the distance of their centres (+-0.5 of the origin
    normalised by its largest coordinate) to the view; within an octant the
    cubes keep their list order."""
    o = np.asarray(origin, np.float32)
    o_n = (o / np.maximum(np.abs(o).max(), np.float32(1e-6))).astype(
        np.float32)
    signs = np.array([[sx, sy, sz] for sx in (-1, 1) for sy in (-1, 1)
                      for sz in (-1, 1)], np.float32) * np.float32(0.5)
    dist = np.linalg.norm(signs - o_n[None], axis=-1).astype(np.float32)
    rank = np.argsort(np.argsort(dist, kind="stable"), kind="stable")
    octant = ((centers[:, 0] > 0) * 4 + (centers[:, 1] > 0) * 2
              + (centers[:, 2] > 0) * 1)
    return np.lexsort((np.arange(len(centers)), rank[octant]))


@dataclasses.dataclass
class Hits:
    """Every hitting (cube, ray) pair of one view, in scan order."""
    step: np.ndarray        # (P,) scan step = ordered position // chunk
    ray: np.ndarray         # (P,) ray index
    t0: np.ndarray          # (P,) segment start, clipped to near
    t1: np.ndarray          # (P,) segment end
    n_steps: int            # scan steps that hold at least one cube


def step_world(w: dict) -> float:
    return w["step_size"] * (2.0 * w["scene_bound"] / w["occ_res"])


def cube_world(w: dict) -> float:
    return 2.0 * w["scene_bound"] * w["cube_size"] / w["occ_res"]


def samples_per_segment(w: dict) -> int:
    """Samples the renderer lays along one segment: the cube's bounding
    ball diameter over the step, plus one."""
    radius = cube_world(w) * math.sqrt(3.0) / 2.0
    return int(math.ceil(2.0 * radius / step_world(w))) + 1


def hits(w: dict, centers: np.ndarray, rays_o: np.ndarray,
         rays_d: np.ndarray, chunk: int, block: int = 256) -> Hits:
    """Line-slab intersection of every ray with every cube (float32, the
    renderer's arithmetic), cubes in the view's octant order."""
    order = octant_order(centers, rays_o[0])
    ctr = centers[order]
    half = np.float32(cube_world(w) / 2.0)
    near = np.float32(w["near"])
    safe = np.where(np.abs(rays_d) < 1e-9, np.float32(1e-9), rays_d)
    out = {"step": [], "ray": [], "t0": [], "t1": []}
    for s in range(0, len(ctr), block):
        c = ctr[s:s + block]
        ta = (c[:, None] - half - rays_o[None]) / safe[None]
        tb = (c[:, None] + half - rays_o[None]) / safe[None]
        t0 = np.max(np.minimum(ta, tb), axis=-1)
        t1 = np.min(np.maximum(ta, tb), axis=-1)
        ci, ri = np.nonzero((t1 > t0) & (t1 > near))
        out["step"].append((s + ci) // chunk)
        out["ray"].append(ri)
        out["t0"].append(np.maximum(t0[ci, ri], near))
        out["t1"].append(t1[ci, ri])
    cat = {k: np.concatenate(v) if v else np.zeros(0) for k, v in out.items()}
    order = np.argsort(cat["step"], kind="stable")
    return Hits(cat["step"][order].astype(np.int64),
                cat["ray"][order].astype(np.int64),
                cat["t0"][order].astype(np.float32),
                cat["t1"][order].astype(np.float32),
                -(-len(ctr) // chunk))


def sample_ts(w: dict, h: Hits):
    """(P, ns) sample depths and their in-segment mask."""
    ns = samples_per_segment(w)
    ts = h.t0[:, None] + (np.arange(ns, dtype=np.float32)[None]
                          + np.float32(0.5)) * np.float32(step_world(w))
    return ts, ts < h.t1[:, None]
