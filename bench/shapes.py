"""The work a view requires, whatever implements it.

`required_samples`: every in-segment sample of every hitting (ray, cube)
pair, with no early termination and nothing dropped. `ops_per_sample`:
the arithmetic of one sample at the configuration's widths. Bytes count
only what every implementation must move (rays in, pixels out, the MLP's
weights): how factor windows are reused differs by implementation, and a
count that assumed one implementation's traffic would read over 100% of
the roofline after a change that avoided it.
"""
from __future__ import annotations

from bench import geometry


def required_samples(w: dict, h: geometry.Hits) -> int:
    _, mask = geometry.sample_ts(w, h)
    return int(mask.sum())


def ops_per_sample(w: dict) -> dict:
    """Operations (a multiply or an add is one, a transcendental one; a
    sigmoid is negate, exp, add and divide) of one sample, by part."""
    rs, rc, a, hdim = w["r_sigma"], w["r_color"], w["app_dim"], \
        w["mlp_hidden"]
    r = rs + rc
    d_in = 3 + 6 * w["pe_view"] + a * (1 + 2 * w["pe_feat"])
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
        "mlp": 2 * (d_in * hdim + hdim * hdim + hdim * 3)
        + (2 * hdim + 3) + 2 * hdim + 3 * 4,
        # tau, running sum, T, alpha, weight, weighted rgb and its sum
        "composite": 13,
    }


def bytes_per_view(w: dict, n_rays: int) -> int:
    """Rays in (origin and direction), pixels out, the MLP's weights."""
    hdim = w["mlp_hidden"]
    d_in = 3 + 6 * w["pe_view"] + w["app_dim"] * (1 + 2 * w["pe_feat"])
    mlp = d_in * hdim + hdim * hdim + hdim * 3 + 2 * hdim + 3
    return 4 * (6 * n_rays + 3 * n_rays + mlp)
