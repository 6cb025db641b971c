"""Inputs made from the seed and the configuration: the streams of a
run's seed and the scene's cubes (the field's weights come from the
configuration's architecture, `bench/archs/<arch>.py`).

Both sides of the comparison read what this module makes: the program
under test (through `RenderEngine`) and `bench/reference.py`. Nothing here
comes from the program, so the reference never takes a table the program
built.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, stream: int) -> int:
    """A 32-bit key for one named stream of a run's seed. `seed` may be any
    non-negative integer, larger than 32 bits included."""
    return int(np.random.SeedSequence([int(seed), int(stream)])
               .generate_state(1)[0])


def scene_sdf(prims, pts: np.ndarray) -> np.ndarray:
    """Signed distance to the union of the scene's primitives (numpy).
    Each primitive is {"type": "box"|"sphere"|"cylinder", "center": [3],
    "size": [3]}: box half-extents, sphere radius in size[0], cylinder
    (radius, half-height) about z in size[0:2]."""
    d = np.full(pts.shape[0], np.inf, np.float32)
    for p in prims:
        rel = pts - np.asarray(p["center"], np.float32)
        s = np.asarray(p["size"], np.float32)
        if p["type"] == "sphere":
            dp = np.linalg.norm(rel, axis=-1) - s[0]
        else:
            if p["type"] == "box":
                q = np.abs(rel) - s
            elif p["type"] == "cylinder":
                q = np.stack([np.linalg.norm(rel[:, :2], axis=-1) - s[0],
                              np.abs(rel[:, 2]) - s[1]], axis=-1)
            else:
                raise ValueError(f"unknown primitive {p['type']!r}")
            dp = (np.linalg.norm(np.maximum(q, 0.0), axis=-1)
                  + np.minimum(q.max(axis=-1), 0.0))
        d = np.minimum(d, dp)
    return d


def occupancy(conf: dict) -> np.ndarray:
    """(occ_res,)*3 bool: voxels whose centre lies inside the scene."""
    w = conf["field"]
    g, b = w["occ_res"], w["scene_bound"]
    xs = (((np.arange(g) + 0.5) / g) * 2.0 - 1.0).astype(np.float32) * b
    occ = np.zeros((g, g, g), bool)
    yz = np.stack(np.meshgrid(xs, xs, indexing="ij"), -1).reshape(-1, 2)
    for i, x in enumerate(xs):                       # one x-slab at a time
        pts = np.concatenate([np.full((yz.shape[0], 1), x, np.float32), yz],
                             axis=1)
        occ[i] = (scene_sdf(conf["scene"]["primitives"], pts) <= 0.0
                  ).reshape(g, g)
    return occ


def cube_centers(conf: dict, occ: np.ndarray) -> np.ndarray:
    """(n, 3) float32 world centres of the cubes that hold an occupied
    voxel, in the row-major order of the cube grid."""
    w = conf["field"]
    g, cs, b = w["occ_res"], w["cube_size"], w["scene_bound"]
    gc = g // cs
    cube = occ.reshape(gc, cs, gc, cs, gc, cs).any(axis=(1, 3, 5))
    idx = np.argwhere(cube)
    if idx.shape[0] > w["max_cubes"]:
        raise ValueError(f"{idx.shape[0]} occupied cubes exceed max_cubes "
                         f"{w['max_cubes']}")
    return ((idx + 0.5) * (2.0 * b * cs / g) - b).astype(np.float32)
