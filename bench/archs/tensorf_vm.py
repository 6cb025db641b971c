"""TensoRF VM (Chen et al., ECCV 2022), the field RT-NeRF compresses: what
the benchmark needs to know of this architecture, and nothing of any other.

A configuration names its architecture (`"arch"` in
`bench/configs/<name>.json`); the harness loads `bench/archs/<arch>.py` by
file path and calls only what this module provides:

* `make_weights(conf, seed)`: the served parameter set, from the seed;
* `build(conf, w, params) -> (cfg, field)`: the program's configuration
  and served field;
* `reference_field(params, w, pts, dirs)`: the plain float32 reference
  field, at `Precision.HIGHEST`;
* `ops_per_sample(w)` (by part, with the shared `"composite"`) and
  `bytes_per_view(w, n_rays)`: the work a view requires;
* `rehearsal_widths()`: the program's tiny shapes, for a run on the CPU;
* `SCOPES`: the named scopes inside field evaluation that the trace times
  apart (and sums into `rtnerf.field_eval`).

Departures of the reference field from TensoRF, each one the served
renderer's and written here so that the reference renders what the
renderer is specified to render:

* density is softplus of the VM sum with no shift (TensoRF shifts by -10);
* grid coordinates map [-bound, bound] onto [0, G-1] (corner-aligned),
  bilinear on planes, linear on lines, clipped at the border;
* the colour MLP reads (dir, PE(dir), feat, PE(feat)) with sin/cos bands
  2^i, ReLU, ReLU, sigmoid (TensoRF's MLPRender_Fea order).
"""
from __future__ import annotations

import dataclasses

import numpy as np

SCOPES = ("fused.decode", "fused.sample", "fused.accumulate")
FACTOR_KEYS = ("sigma_planes", "sigma_lines", "app_planes", "app_lines")
PLANE_AXES = ((1, 2), (0, 2), (0, 1))   # mode m: plane over these axes,
LINE_AXES = (0, 1, 2)                   # line along this one


def rehearsal_widths() -> dict:
    """The program's tiny CI shapes, for a run on the CPU."""
    from repro.configs.rtnerf import demo_config
    tiny = dataclasses.asdict(demo_config(tiny=True))
    keys = ("grid_res", "occ_res", "cube_size", "max_cubes", "r_sigma",
            "r_color", "app_dim", "mlp_hidden")
    return {k: tiny[k] for k in keys}


def build(conf: dict, w: dict, params):
    """(the program's NeRFConfig, its dense field over `params`); the
    engine encodes the field when the configuration says so."""
    from repro.configs.rtnerf import NeRFConfig
    from repro.core import field as field_lib
    cfg = NeRFConfig(**w)
    return cfg, field_lib.DenseField(params, cfg)


def shapes(w: dict) -> dict:
    """Shape of every leaf of the TensoRF VM parameter set, from the
    configuration's widths (`w` is the configuration's "field" object)."""
    g, rs, rc = w["grid_res"], w["r_sigma"], w["r_color"]
    h = w["mlp_hidden"]
    return {
        "sigma_planes": (3, rs, g, g), "sigma_lines": (3, rs, g),
        "app_planes": (3, rc, g, g), "app_lines": (3, rc, g),
        "basis": (3 * rc, w["app_dim"]),
        "mlp_w1": (_mlp_in(w), h), "mlp_b1": (h,),
        "mlp_w2": (h, h), "mlp_b2": (h,),
        "mlp_w3": (h, 3), "mlp_b3": (3,),
    }


def _mlp_in(w: dict) -> int:
    return 3 + 6 * w["pe_view"] + w["app_dim"] * (1 + 2 * w["pe_feat"])


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

    from bench.inputs import seed_words

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

    def build_all(key):
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
    return jax.jit(build_all)(key)


def _bands(x, n_bands):
    import jax.numpy as jnp
    out = [x]
    for i in range(n_bands):
        out += [jnp.sin((2.0 ** i) * x), jnp.cos((2.0 ** i) * x)]
    return jnp.concatenate(out, axis=-1)


def _vm(planes, lines, g):
    """Per-point VM components (N, 3*R) from grid coords g (N, 3)."""
    import jax.numpy as jnp
    G = planes.shape[-1]
    g = jnp.clip(g, 0.0, G - 1.0)
    i0 = jnp.clip(jnp.floor(g).astype(jnp.int32), 0, G - 2)
    f = g - i0
    comps = []
    for m in range(3):
        a, b = PLANE_AXES[m]
        c = LINE_AXES[m]
        P, L = planes[m], lines[m]                     # (R,G,G), (R,G)
        ua, ub, uc = i0[:, a], i0[:, b], i0[:, c]
        fa, fb, fc = f[:, a, None], f[:, b, None], f[:, c, None]
        pv = (P[:, ua, ub].T * (1 - fa) * (1 - fb)
              + P[:, ua, ub + 1].T * (1 - fa) * fb
              + P[:, ua + 1, ub].T * fa * (1 - fb)
              + P[:, ua + 1, ub + 1].T * fa * fb)
        lv = L[:, uc].T * (1 - fc) + L[:, uc + 1].T * fc
        comps.append(pv * lv)
    return jnp.concatenate(comps, axis=-1)


def reference_field(params, w: dict, pts, dirs):
    """(sigma (N,), rgb (N, 3)) of the field at world points."""
    import jax
    import jax.numpy as jnp

    from bench.reference import matmul
    g = (pts / w["scene_bound"] * 0.5 + 0.5) * (w["grid_res"] - 1)
    sigma = jax.nn.softplus(jnp.sum(
        _vm(params["sigma_planes"], params["sigma_lines"], g), axis=-1))
    feat = matmul(_vm(params["app_planes"], params["app_lines"], g),
                  params["basis"])
    x = jnp.concatenate([_bands(dirs, w["pe_view"]),
                         _bands(feat, w["pe_feat"])], axis=-1)
    h = jax.nn.relu(matmul(x, params["mlp_w1"]) + params["mlp_b1"])
    h = jax.nn.relu(matmul(h, params["mlp_w2"]) + params["mlp_b2"])
    rgb = jax.nn.sigmoid(matmul(h, params["mlp_w3"]) + params["mlp_b3"])
    return sigma, rgb


def ops_per_sample(w: dict) -> dict:
    """Operations (a multiply or an add is one, a transcendental one; a
    sigmoid is negate, exp, add and divide) of one sample, by part."""
    rs, rc, a, hdim = w["r_sigma"], w["r_color"], w["app_dim"], \
        w["mlp_hidden"]
    r = rs + rc
    return {
        # world -> grid: scale, offset, scale per axis
        "grid": 9,
        # per mode: 4 bilinear + 1 linear weight (6 + 1 ops), then per
        # component 4 corner products + 3 adds, 2 + 1 on the line, 1 product
        "vm": 3 * (7 + r * 11),
        # density: sum of 3*Rs products, softplus
        "density": 3 * rs - 1 + 1,
        "basis": 2 * 3 * rc * a,
        # a band: one scale, sin, cos per input
        "pe": 3 * (3 * w["pe_view"] + a * w["pe_feat"]),
        "mlp": 2 * (_mlp_in(w) * hdim + hdim * hdim + hdim * 3)
        + (2 * hdim + 3) + 2 * hdim + 3 * 4,
        # tau, running sum, T, alpha, weight, weighted rgb and its sum
        "composite": 13,
    }


def bytes_per_view(w: dict, n_rays: int) -> int:
    """Rays in (origin and direction), pixels out, the MLP's weights: how
    factor windows are reused differs by implementation, and a count that
    assumed one implementation's traffic would read over 100% of the
    roofline after a change that avoided it."""
    hdim = w["mlp_hidden"]
    mlp = _mlp_in(w) * hdim + hdim * hdim + hdim * 3 + 2 * hdim + 3
    return 4 * (6 * n_rays + 3 * n_rays + mlp)
