"""Plain reference of the served view: the configuration's field rendered
through RT-NeRF's per-cube segments.

Straightforward float32 `jax.numpy`, every matmul at full float32
(`Precision.HIGHEST`), with no compaction, pair budget, micro-batching or
sparse encoding: every hitting (ray, cube) pair of `bench/geometry.py` is
evaluated, in blocks of pairs so that it fits. The field itself is the
architecture's reference field (`bench/archs/<arch>.py`, with its own
departures), over the weights the architecture made from the seed. This
module holds what every architecture shares, and imports nothing of the
program.

Departures, each one the served renderer's and written here so that the
reference renders what the renderer is specified to render:

* samples lie at t0 + (k + 1/2) * step inside each cube's slab segment,
  t0 clipped to `near`; a segment composites front to back within itself;
* cubes are composited `cube_chunk` at a time in the view's octant order
  (RT-NeRF Sec. 3.2), and every cube of one chunk sees the transmittance
  from before the chunk (the renderer's documented approximation for
  chunk > 1);
* a ray whose transmittance before a chunk is at or below `term_eps`
  takes nothing from that chunk on (early termination);
* white background: the final transmittance is added to every channel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import geometry

def matmul(x, y):
    """Full float32 matmul on every backend (`Precision.HIGHEST`)."""
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _segments(field, params, w, pts, dirs, mask, delta):
    """Per-pair colour and optical depth of each segment (P,3), (P,)."""
    p, ns = mask.shape
    sigma, rgb = field(params, w, pts.reshape(-1, 3),
                       jnp.repeat(dirs, ns, axis=0))
    sigma = jnp.where(mask, sigma.reshape(p, ns), 0.0)
    tau = sigma * delta
    cum = jnp.cumsum(tau, axis=-1)
    wgt = jnp.exp(-(cum - tau)) * (1.0 - jnp.exp(-tau))
    seg_rgb = jnp.sum(wgt[..., None] * rgb.reshape(p, ns, 3), axis=1)
    return seg_rgb, cum[:, -1]


def render(field, params, w: dict, h: geometry.Hits, rays_o: np.ndarray,
           rays_d: np.ndarray, *, dtype: str = "float32",
           block: int = 8192):
    """(image (N, 3) float32, {ray: (m, 3) colours it may take}) of one
    view from its hits (`geometry.hits`), with `field(params, w, pts,
    dirs) -> (sigma, rgb)` the architecture's reference field. A sample
    within a few float32 ulps of its segment's end lies on either side of
    it on a chip whose division or fused multiply-add rounds otherwise than
    numpy's; every ray that holds such samples gets the colour of each way
    they may fall.
    A lower `dtype` gives a control that must come out not correct."""
    dt = jnp.dtype(dtype)
    ts, mask = geometry.sample_ts(w, h)
    flip = np.abs(ts - h.t1[:, None]) <= np.float32(1e-6) * h.t1[:, None]
    prm = {k: jnp.asarray(v, dt) for k, v in params.items()}
    seg = _segment_table(field, prm, w, h, ts, mask, rays_o, rays_d, dt,
                         block)
    img = _composite(w, h, *seg, len(rays_o))
    amb = np.nonzero(flip.any(axis=1))[0]
    if not len(amb):
        return img, {}
    sub = geometry.Hits(h.step[amb], h.ray[amb], h.t0[amb], h.t1[amb],
                        h.n_steps)
    flipped = _segment_table(field, prm, w, sub, ts[amb],
                             mask[amb] ^ flip[amb], rays_o, rays_d, dt,
                             block)
    alt_of = {int(p): (flipped[0][i], flipped[1][i])
              for i, p in enumerate(amb)}
    alts = {}
    for r in np.unique(h.ray[amb]):
        pairs = np.nonzero(h.ray == r)[0]
        choices = [p for p in pairs if int(p) in alt_of][:MAX_AMBIGUOUS]
        cols = []
        for bits in range(1 << len(choices)):
            rgb, tau = seg[0][pairs].copy(), seg[1][pairs].copy()
            for j, p in enumerate(choices):
                if bits >> j & 1:
                    k = int(np.searchsorted(pairs, p))
                    rgb[k], tau[k] = alt_of[int(p)]
            cols.append(_composite_ray(w, h.step[pairs], rgb, tau))
        alts[int(r)] = np.stack(cols)
    return img, alts


MAX_AMBIGUOUS = 6            # samples per ray tried both ways, at most


def _segment_table(field, prm, w, h, ts, mask, rays_o, rays_d, dt,
                   block):
    """(P, 3) colour and (P,) optical depth of every pair's segment."""
    frozen = _Frozen(tuple(sorted(w.items())))
    delta = jnp.asarray(geometry.step_world(w), dt)
    n = len(h.ray)
    seg_rgb = np.zeros((n, 3), np.float32)
    seg_tau = np.zeros((n,), np.float32)
    for s in range(0, n, block):
        e = min(s + block, n)
        ri = h.ray[s:e]
        pts = rays_o[ri, None] + rays_d[ri, None] * ts[s:e, :, None]
        pad = block - (e - s)            # one block shape: one compile
        args = [np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                for a in (pts, rays_d[ri], mask[s:e])]
        r, t = _segments(field, prm, frozen, jnp.asarray(args[0], dt),
                         jnp.asarray(args[1], dt), jnp.asarray(args[2]),
                         delta)
        seg_rgb[s:e] = np.asarray(r.astype(jnp.float32))[:e - s]
        seg_tau[s:e] = np.asarray(t.astype(jnp.float32))[:e - s]
    return seg_rgb, seg_tau


def _composite(w, h: geometry.Hits, seg_rgb, seg_tau, n_rays: int):
    """Chunk after chunk in scan order: every pair of a chunk sees its
    ray's transmittance from before the chunk; white background."""
    log_t = np.zeros(n_rays, np.float32)
    color = np.zeros((n_rays, 3), np.float32)
    bounds = np.searchsorted(h.step, np.arange(h.n_steps + 1))
    for s in range(h.n_steps):
        a, b = bounds[s], bounds[s + 1]
        if a == b:
            continue
        ri = h.ray[a:b]
        t_pre = np.exp(log_t)
        live = t_pre[ri] > w["term_eps"]
        ri = ri[live]
        np.add.at(color, ri, t_pre[ri, None] * seg_rgb[a:b][live])
        np.add.at(log_t, ri, -seg_tau[a:b][live])
    return color + np.exp(log_t)[:, None]


def _composite_ray(w, steps, seg_rgb, seg_tau):
    """`_composite` for the pairs of one ray."""
    log_t, color = np.float32(0.0), np.zeros(3, np.float32)
    for s in np.unique(steps):
        t_pre = np.exp(log_t)
        if t_pre <= w["term_eps"]:
            continue
        at = steps == s
        color = color + t_pre * seg_rgb[at].sum(axis=0)
        log_t = log_t - seg_tau[at].sum()
    return color + np.exp(log_t)


class _Frozen(dict):
    """The configuration's widths as a hashable static jit argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = items

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
