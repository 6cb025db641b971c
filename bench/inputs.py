"""Inputs made from the seed: the field's weights and the scene's cubes.

Both sides of the comparison read what this module makes: the program
under test (through `RenderEngine`) and `bench/reference.py`. Nothing here
comes from the program, so the reference never takes a table the program
built.
"""
from __future__ import annotations

import numpy as np

FACTOR_KEYS = ("sigma_planes", "sigma_lines", "app_planes", "app_lines")


def seed_words(seed: int, stream: int) -> int:
    """A 32-bit key for one named stream of a run's seed. `seed` may be any
    non-negative integer, larger than 32 bits included."""
    return int(np.random.SeedSequence([int(seed), int(stream)])
               .generate_state(1)[0])


def shapes(w: dict) -> dict:
    """Shape of every leaf of the TensoRF VM parameter set, from the
    configuration's widths (`w` is the configuration's "field" object)."""
    g, rs, rc = w["grid_res"], w["r_sigma"], w["r_color"]
    d_in = 3 + 6 * w["pe_view"] + w["app_dim"] * (1 + 2 * w["pe_feat"])
    h = w["mlp_hidden"]
    return {
        "sigma_planes": (3, rs, g, g), "sigma_lines": (3, rs, g),
        "app_planes": (3, rc, g, g), "app_lines": (3, rc, g),
        "basis": (3 * rc, w["app_dim"]),
        "mlp_w1": (d_in, h), "mlp_b1": (h,),
        "mlp_w2": (h, h), "mlp_b2": (h,),
        "mlp_w3": (h, 3), "mlp_b3": (3,),
    }


def make_weights(conf: dict, seed: int):
    """The pruned float32 parameter set, made on the device in one jitted
    call. Factors are N(0, s^2) with the configuration's `init_scale` per
    key; each mode slice of a factor key keeps exactly its largest
    (1 - sparsity) share of entries by magnitude (the rule of
    `tensorf.prune_to_sparsity`, applied per slice so that every seed gives
    the same number of non-zeros and so the same encoded shapes). Matrices
    are fan-in scaled normals, biases zero."""
    import jax
    import jax.numpy as jnp

    shp = shapes(conf["field"])
    scale = conf["init_scale"]
    keep = {k: 1.0 - conf["sparsity"][k] for k in FACTOR_KEYS}

    def prune(w, frac):
        flat = w.reshape(w.shape[0], -1)                 # one row per mode
        k = int(round(frac * flat.shape[1]))
        # exactly k kept per row, by rank: equal magnitudes at the cut
        # (+x and -x) must not change the count, and with it the shapes
        keep_idx = jnp.argsort(-jnp.abs(flat), axis=1)[:, :k]
        rows = jnp.arange(flat.shape[0])[:, None]
        kept = jnp.zeros(flat.shape, bool).at[rows, keep_idx].set(True)
        return jnp.where(kept, flat, 0.0).reshape(w.shape)

    def build(key):
        keys = dict(zip(sorted(shp), jax.random.split(key, len(shp))))
        out = {}
        for name, s in shp.items():
            if name in FACTOR_KEYS:
                w = jax.random.normal(keys[name], s, jnp.float32) * scale[name]
                out[name] = prune(w, keep[name])
            elif name.startswith("mlp_b"):
                out[name] = jnp.zeros(s, jnp.float32)
            else:
                out[name] = (jax.random.normal(keys[name], s, jnp.float32)
                             / np.sqrt(s[0]))
        return out

    key = jax.random.PRNGKey(seed_words(seed, 0))
    return jax.jit(build)(key)


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
