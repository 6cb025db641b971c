"""RT-NeRF's efficient rendering pipeline (paper Sec. 3.1) and the
coarse-grained view-dependent rendering ordering (Sec. 3.2).

API: `render_rtnerf(field, cfg, cubes, cam)` renders one view image-space;
`make_ray_renderer(cfg, chunk=...)` builds the jit-able fixed-shape ray
step the serving engine compiles once; `order_cubes` / `octant_rank` /
`ordering_key` implement the Sec. 3.2 ordering and its exact reuse key;
`OrderingCache` memoises per-view schedules across a request stream
(ROADMAP "streaming / multi-view compressed serving"). `field` is anything
`field.as_backend` accepts — encoded fields are sampled in place.

Instead of uniformly sampling N points along each of H*W rays and querying
the occupancy grid H*W*N times, we loop over the *non-zero cubes* of the
occupancy grid (CubeSet, computed at occupancy-update time):

  Step 2-1-a  approximate each cube by its bounding ball,
  Step 2-1-b  project the ball to the image plane as an oval (we use the
              conservative bounding circle of the oval — JAX needs a static
              pixel tile; see DESIGN.md §3),
  Step 2-1-c  the pixels inside the oval, realised as a static TILE x TILE
              pixel window around the projected center with an in-circle mask,
  Step 2-1-d  analytic line-sphere intersection per (pixel-ray, ball) giving
              the sample segment [t_in, t_out].

Cubes are processed front-to-back in the view-dependent order (octants of
the scene, nearest first — Sec. 3.2), so per-pixel transmittance is known
when a cube is reached and invisible points (T <= eps) are skipped. Only the
running (T, partial color) per pixel is kept — no per-point feature buffer.

`chunk` > 1 composites that many cubes per scan step; cubes are spatially
disjoint so this is exact unless two same-chunk cubes overlap the same pixel
(rare under front-to-back ordering; chunk=1 is exact and is the default).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core.occupancy import CubeSet
from repro.core.rendering import Camera, composite, pixel_rays, step_world


# --------------------------------------------------------------------------
# Sec. 3.2 — view-dependent ordering
# --------------------------------------------------------------------------


def octant_rank(origin):
    """Sec. 3.2 octant priorities: rank of each of the 8 scene octants by
    distance of its center to the (normalised) view origin. Host-side
    numpy, and the ONLY implementation — both `order_cubes` (to build the
    schedule) and `ordering_key` (to cache it) consume this, so a cache key
    can never disagree with the schedule it stands for."""
    o = np.asarray(origin, np.float32).reshape(-1)
    o_n = (o / np.maximum(np.abs(o).max(), np.float32(1e-6))).astype(
        np.float32)
    signs = np.array([[sx, sy, sz] for sx in (-1, 1) for sy in (-1, 1)
                      for sz in (-1, 1)], np.float32) * np.float32(0.5)
    d = np.linalg.norm(signs - o_n[None], axis=-1).astype(np.float32)
    return tuple(int(r) for r in np.argsort(np.argsort(d, kind="stable"),
                                            kind="stable"))


def ordering_key(origin, mode: str = "octant", quantum: float = 0.25):
    """Hashable cache key that determines `order_cubes`' output exactly.

    mode="octant": the permutation depends only on the octant ranking
    (within an octant, cubes keep the fixed scan order), so the
    `octant_rank` tuple is an exact reuse key: finitely many schedules,
    shared by every view that ranks octants alike. Keying on the origin's
    octant alone would NOT be sound — two cameras in one octant with
    different dominant axes rank the octants differently, and compositing
    disjoint segments out of order leaks occluded geometry.

    mode="trajectory": the streaming key — the origin quantised to a
    `quantum`-sized grid, so consecutive cameras on a smooth head-tracked
    path share a key (and near-misses are caught by the OrderingCache's
    nearest-neighbour fallback). The schedule itself is the octant
    ordering (exact for the origin that computed it); reusing it from a
    neighbouring pose is the trajectory-level approximation — bounded by
    the quantum, and only ever wrong in the rare case a sub-quantum move
    flips the octant ranking mid-cell.

    mode="distance": the per-cube sort depends on the full origin; key by
    its rounded coordinates (reuse only for effectively identical views).
    """
    if mode == "trajectory":
        o = np.asarray(origin, np.float64).reshape(-1)
        return tuple(int(q) for q in np.round(o / float(quantum)))
    if mode != "octant":
        return tuple(np.round(np.asarray(origin, np.float64), 6).tolist())
    return octant_rank(origin)


class OrderingCache:
    """Cache of per-view `order_cubes` schedules (Sec. 3.2 reuse).

    One entry per `ordering_key`: the first request with a given octant
    ranking computes the front-to-back permutation (and the permuted cube
    arrays, so consumers don't re-gather them); every later view that ranks
    the octants identically reuses it bit-exactly — the paper's
    coarse-grained view-dependent ordering as a cache. `invalidate()` must
    be called when the cube set changes (occupancy rebuild).

    `max_entries` bounds the resident set LRU-style: octant mode has
    finitely many keys anyway, but distance mode keys on the full origin
    and would otherwise grow without bound under a free camera stream.

    mode="trajectory" is the streaming extension (ROADMAP "frame-coherent
    AR/VR streaming"): keys are the origin quantised to `pose_quantum`,
    and an exact-key miss falls back to the nearest cached pose within
    `nn_radius` quanta before recomputing `order_cubes` — so a smooth
    head-tracked path reuses one schedule per neighbourhood instead of
    recomputing per frame. The NN tie-break is (distance, key), not
    insertion order, so lookups are deterministic regardless of LRU churn.

    `scene` is an optional label (the serving SceneStore keys one cache per
    resident scene); `with_cubes(cubes)` is the rebuild path — a NEW cache
    over the new cube set that carries the hit/miss counters forward, so an
    in-flight render keeps its old cache consistent while telemetry stays
    cumulative across occupancy rebuilds and field swaps. When a metrics
    `registry` is supplied, hits and misses are additionally exported as
    `ordering_cache_hits`/`ordering_cache_misses` counters (labelled by
    scene), so cache effectiveness is visible in the exposition endpoints
    — not only in `stats()` polls.
    """

    def __init__(self, cubes: CubeSet, mode: str = "octant",
                 max_entries: int = 64, scene: Optional[str] = None, *,
                 pose_quantum: float = 0.25, nn_radius: float = 1.5,
                 registry=None):
        import collections

        self.cubes = cubes
        self.mode = mode
        self.scene = scene
        self.max_entries = int(max_entries)
        self.pose_quantum = float(pose_quantum)
        self.nn_radius = float(nn_radius)
        self.registry = registry
        self._entries = collections.OrderedDict()  # key -> (perm, ctr, vld)
        self.hits = 0
        self.misses = 0
        self.nn_hits = 0            # subset of hits served by NN fallback
        self._c_hits = self._c_misses = None
        if registry is not None:
            labels = {"scene": scene} if scene is not None else {}
            self._c_hits = registry.counter("ordering_cache_hits", **labels)
            self._c_misses = registry.counter("ordering_cache_misses",
                                              **labels)

    def with_cubes(self, cubes: CubeSet) -> "OrderingCache":
        """Fresh (empty) cache over `cubes`, counters carried over — the
        cube-set-changed path (occupancy rebuild / field swap). A new object
        rather than invalidate-in-place so a snapshot taken before the swap
        keeps rendering from a consistent (cubes, ordering) pair."""
        nxt = OrderingCache(cubes, self.mode, self.max_entries, self.scene,
                            pose_quantum=self.pose_quantum,
                            nn_radius=self.nn_radius, registry=self.registry)
        nxt.hits, nxt.misses, nxt.nn_hits = (self.hits, self.misses,
                                             self.nn_hits)
        return nxt

    def key_for(self, origin) -> tuple:
        return ordering_key(origin, self.mode, self.pose_quantum)

    def _nearest(self, k: tuple):
        """Nearest cached key within `nn_radius` quanta of `k`, or None.
        Tie-break on (distance, key) so the winner doesn't depend on LRU
        order — two passes over the same cache contents pick the same
        entry."""
        best = None
        for k2 in self._entries:
            d = math.dist(k, k2)
            if d <= self.nn_radius and (best is None or (d, k2) < best):
                best = (d, k2)
        return None if best is None else best[1]

    def _note(self, hit: bool, nn: bool = False):
        if hit:
            self.hits += 1
            self.nn_hits += int(nn)
            if self._c_hits is not None:
                self._c_hits.inc()
        else:
            self.misses += 1
            if self._c_misses is not None:
                self._c_misses.inc()

    def _lookup(self, origin) -> tuple:
        k = self.key_for(origin)
        e = self._entries.get(k)
        if e is None and self.mode == "trajectory":
            k_nn = self._nearest(k)
            if k_nn is not None:
                self._note(hit=True, nn=True)
                self._entries.move_to_end(k_nn)
                return self._entries[k_nn]
        if e is None:
            self._note(hit=False)
            perm = order_cubes(self.cubes,
                               jnp.asarray(origin, jnp.float32), self.mode)
            e = (perm, self.cubes.centers[perm], self.cubes.valid[perm])
            self._entries[k] = e
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)      # evict LRU
        else:
            self._note(hit=True)
            self._entries.move_to_end(k)
        return e

    def get(self, origin) -> jax.Array:
        """This view's front-to-back cube permutation."""
        return self._lookup(origin)[0]

    def get_ordered(self, origin):
        """The permuted (centers, valid) arrays for this view."""
        _, centers, valid = self._lookup(origin)
        return centers, valid

    def invalidate(self, cubes: CubeSet = None):
        self._entries.clear()
        if cubes is not None:
            self.cubes = cubes

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "nn_hits": self.nn_hits, "entries": len(self._entries)}


def order_cubes(cubes: CubeSet, origin: jax.Array, mode: str = "octant"):
    """Front-to-back permutation of the cube list for this view.

    mode="octant": the paper's coarse scheme — 8 sub-spaces ranked by
    distance of their centers to the view origin (`octant_rank`, host-side:
    the origin is concrete at schedule-build time); cubes keep their fixed
    scan order within an octant (regular DRAM access pattern).
    mode="trajectory": the octant schedule, cached under quantised-pose
    keys by OrderingCache (the streaming tier's reuse mode).
    mode="distance": per-cube distance sort (finer; beyond-paper).
    """
    c = cubes.centers
    if mode in ("octant", "trajectory"):
        oct_id = ((c[:, 0] > 0).astype(jnp.int32) * 4
                  + (c[:, 1] > 0).astype(jnp.int32) * 2
                  + (c[:, 2] > 0).astype(jnp.int32))
        rank = jnp.asarray(octant_rank(origin), jnp.float32)
        key = rank[oct_id] * (c.shape[0] + 1.0) \
            + jnp.arange(c.shape[0], dtype=jnp.float32)
    else:
        key = jnp.linalg.norm(c - origin[None], axis=-1)
    key = jnp.where(cubes.valid, key, jnp.inf)            # invalid last
    perm = jnp.argsort(key)
    return perm


# --------------------------------------------------------------------------
# Sec. 3.1 — geometry of pre-existing points from non-zero cubes
# --------------------------------------------------------------------------


def auto_tile(cfg: NeRFConfig, cam: Camera) -> int:
    """Static tile size covering the projected ball at the near plane."""
    r_pix = cam.focal * cfg.cube_ball_radius() / max(cfg.near - cfg.scene_bound * 0.0
                                                     - cfg.cube_ball_radius(), 0.5)
    t = int(math.ceil(2.0 * r_pix / 8.0) * 8 + 8)
    return max(8, min(t, 128))


def samples_per_segment(cfg: NeRFConfig) -> int:
    """Static bound on samples inside one ball: ceil(2r / step)."""
    return int(math.ceil(2.0 * cfg.cube_ball_radius() / step_world(cfg))) + 1


def _cube_samples(cfg: NeRFConfig, cam: Camera, center, tile: int,
                  intersect: str = "box"):
    """Steps 2-1-b/c/d for ONE cube. Returns per-tile-pixel sample geometry.

    intersect="ball" is the paper's Step 2-1-d (line-sphere); "box" clips the
    sample segment to the cube itself (line-slab, also analytic), which
    removes the double-counting of overlapping bounding balls — a measured
    beyond-paper accuracy fix (EXPERIMENTS.md §NeRF-ablations).
    """
    # project center
    rel = (center - cam.origin) @ cam.c2w                 # camera coords
    depth = -rel[2]
    r = cfg.cube_ball_radius()
    safe_depth = jnp.maximum(depth - r, 0.1)
    cx = rel[0] / safe_depth * cam.focal + cam.w / 2.0
    cy = -rel[1] / safe_depth * cam.focal + cam.h / 2.0
    r_pix = cam.focal * r / safe_depth

    # static TILE x TILE window around the projected center (Step 2-1-c)
    half = tile // 2
    x0 = jnp.clip(jnp.round(cx).astype(jnp.int32) - half, 0, max(cam.w - tile, 0))
    y0 = jnp.clip(jnp.round(cy).astype(jnp.int32) - half, 0, max(cam.h - tile, 0))
    dx = jnp.arange(tile)
    px = (x0 + dx)[None, :] * jnp.ones((tile, 1), jnp.int32)
    py = (y0 + dx)[:, None] * jnp.ones((1, tile), jnp.int32)
    px = px.reshape(-1)
    py = py.reshape(-1)
    in_oval = (px - cx) ** 2 + (py - cy) ** 2 <= (r_pix + 1.0) ** 2
    in_img = (px < cam.w) & (py < cam.h)
    pix_id = py * cam.w + px

    # Step 2-1-d: analytic intersection (line-sphere or line-slab)
    d = pixel_rays(cam, px.astype(jnp.float32), py.astype(jnp.float32))
    if intersect == "ball":
        oc = cam.origin - center
        b = jnp.einsum("pd,d->p", d, oc)
        disc = b * b - (jnp.dot(oc, oc) - r * r)
        hit_geo = disc > 0.0
        sq = jnp.sqrt(jnp.maximum(disc, 0.0))
        t0 = -b - sq
        t1 = -b + sq
    else:                                             # exact cube slabs
        half = cfg.cube_world() / 2.0
        safe_d = jnp.where(jnp.abs(d) < 1e-9, 1e-9, d)
        ta = (center[None] - half - cam.origin[None]) / safe_d
        tb = (center[None] + half - cam.origin[None]) / safe_d
        t0 = jnp.max(jnp.minimum(ta, tb), axis=-1)
        t1 = jnp.min(jnp.maximum(ta, tb), axis=-1)
        hit_geo = t1 > t0
    hit = hit_geo & in_oval & in_img & (depth > cfg.near * 0.5)
    t0 = jnp.maximum(t0, cfg.near)

    ns = samples_per_segment(cfg)
    delta = step_world(cfg)
    ts = t0[:, None] + (jnp.arange(ns)[None, :] + 0.5) * delta
    s_mask = hit[:, None] & (ts < t1[:, None])            # (P, ns)
    pts = cam.origin[None, None] + d[:, None] * ts[..., None]
    return pix_id, d, pts, ts, s_mask


def compact_select(flat_hit: jax.Array, budget: int) -> jax.Array:
    """Deterministic active-pair selection: the indices of hitting pairs
    first (in ascending pair order), cut to the static `budget`.

    Sorting on the composite key `miss * n + index` makes every key unique,
    so the result cannot depend on any backend's sort stability or
    tie-breaking — the same hit mask selects the same pair set on CPU, TPU,
    and under the numpy oracle (`np.argsort(~hits, kind="stable")`), which
    is what makes dropped-pair choice (and with it the rendered image)
    reproducible across jit invocations and backends."""
    n = flat_hit.shape[0]
    key = ((~flat_hit).astype(jnp.int32) * n
           + jnp.arange(n, dtype=jnp.int32))
    return jnp.argsort(key)[:budget]


def make_ray_renderer(cfg: NeRFConfig, *, chunk: int = 8,
                      pair_budget: int = None, white_bg: bool = True):
    """Ray-centric RT-NeRF renderer (serving path).

    Returns `render(field, centers, valid, rays_o, rays_d) -> (rgb, aux)`
    where `field` is any FieldBackend (a registered pytree, so under
    `jax.jit` a swapped-in field with the same encoded structure reuses the
    compiled step — the serving engine's `swap_field` path), centers/valid
    are the *ordered* cube arrays (apply an order_cubes permutation first —
    e.g. from an OrderingCache) and rays are an arbitrary batch, so one
    jitted instance serves micro-batched rays from many queued views at a
    fixed chunk shape.

    Geometry is the pipeline's exact line-slab intersection (Step 2-1-d,
    intersect="box") per (cube, ray) instead of per (cube, tile-pixel): no
    tile clipping or oval mask, so accuracy is >= the image-space path.
    Early termination and the chunk>1 overlap approximation match
    `render_rtnerf` exactly.

    Sec. 3.1's "process only pre-existing points" is realised by active-pair
    compaction: per scan step the (chunk, N) ray-cube pairs are tested
    geometrically (cheap) and only the hitting pairs — gathered into a
    static `pair_budget` — go through the field/MLP evaluation (expensive).
    Typical scenes hit a few % of pairs, so this is the serving path's main
    algorithmic win over the per-view loop. Pairs beyond the budget are
    dropped and counted in `aux["dropped_pairs"]` (0 in every measured
    scene at the default budget of chunk*N // 4); `aux["active_pairs_max"]`
    is the max hitting-pair count over the scan steps — the occupancy
    signal the serving engine's adaptive pair-budget loop reads to size the
    budget to the scene instead of the static default.

    Every scan step evaluates the whole budget, hits or not, so `aux` also
    counts the work done against the work that carried a hit (int32
    scalars, counted on the device): `scan_steps`, `pair_slots` (steps x
    budget) and `sample_slots` (pair_slots x samples a segment) are what
    the step evaluates; `live_steps` the steps whose cube chunk holds a
    valid cube, `hit_pairs` the evaluated pairs that hit (min(hits,
    budget) summed over steps), `processed_samples` their in-segment
    samples.

    The field is an argument, not a closure: trace once, serve many, swap
    freely. `aux` carries per-ray transmittance, depth and opacity besides
    the counters.
    """
    delta = step_world(cfg)
    ns = samples_per_segment(cfg)
    half = cfg.cube_world() / 2.0

    def render(field, centers, valid, rays_o, rays_d):
        f = field_lib.as_backend(field, cfg)
        n_rays = rays_o.shape[0]
        nc = centers.shape[0]
        # pad (never truncate) the cube list to a chunk multiple: a
        # non-divisible cube_chunk must not silently drop trailing cubes
        pad = (-nc) % chunk
        if pad:
            centers = jnp.concatenate(
                [centers, jnp.zeros((pad, 3), centers.dtype)])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        n_chunks = (nc + pad) // chunk
        n_pairs = chunk * n_rays
        budget = min(pair_budget or max(n_pairs // 4, 128), n_pairs)

        # named_scope markers (zero runtime cost) tag the HLO, so a
        # profile (serve --profile-dir) names each device op's phase; the
        # engine's stages reach the same profile as host annotations
        # (repro/obs/tracing.py, docs/observability.md)
        def body(carry, xs):
            (log_t, color, depth, processed, dropped, pairs_max, live,
             hit_pairs) = carry
            ctr, vld = xs                                 # (chunk,3),(chunk,)

            # Step 2-1-d: line-slab intersection of every ray with each cube
            with jax.named_scope("rtnerf.intersect"):
                safe_d = jnp.where(jnp.abs(rays_d) < 1e-9, 1e-9, rays_d)
                ta = (ctr[:, None] - half - rays_o[None]) / safe_d[None]
                tb = (ctr[:, None] + half - rays_o[None]) / safe_d[None]
                t0 = jnp.max(jnp.minimum(ta, tb), axis=-1)  # (chunk,N)
                t1 = jnp.min(jnp.maximum(ta, tb), axis=-1)
                alive = jnp.exp(log_t) > cfg.term_eps       # (N,)
                # t1 > near: cubes behind the camera / inside the near plane
                # yield no samples and must not consume pair-budget slots
                hit = (t1 > t0) & (t1 > cfg.near) & vld[:, None] & alive[None]
                t0 = jnp.maximum(t0, cfg.near)

            # active-pair compaction: hitting pairs first (stable), cut to
            # the static budget, evaluate the field only there
            with jax.named_scope("rtnerf.compact"):
                flat_hit = hit.reshape(-1)                # (chunk*N,)
                idx = compact_select(flat_hit, budget)    # hits lead
                sel = flat_hit[idx]                       # (budget,)
                ray_i = idx % n_rays
                t0s = t0.reshape(-1)[idx]
                t1s = t1.reshape(-1)[idx]
                ro_s = rays_o[ray_i]
                rd_s = rays_d[ray_i]

                ts = t0s[:, None] + (jnp.arange(ns)[None] + 0.5) * delta
                s_mask = sel[:, None] & (ts < t1s[:, None])  # (budget,ns)
                pts = ro_s[:, None] + rd_s[:, None] * ts[..., None]
                flat = pts.reshape(-1, 3)
                # points grouped by chunk-local cube (idx // n_rays) so
                # encoded fields stream per-cube factor windows through the
                # fused kernel; non-selected pairs land out-of-window and
                # are masked below
                cube_i = (idx // n_rays).astype(jnp.int32)
                cid = jnp.broadcast_to(cube_i[:, None],
                                       s_mask.shape).reshape(-1)
            with jax.named_scope("rtnerf.field_eval"):
                sigma, feats = f.sigma_app(flat, ctr, cid)
                sigma = jnp.where(s_mask, sigma.reshape(s_mask.shape), 0.0)
                dirs = jnp.broadcast_to(rd_s[:, None],
                                        pts.shape).reshape(-1, 3)
                rgb = f.color(feats, dirs).reshape(*s_mask.shape, 3)

            # per-pair local compositing along the segment
            with jax.named_scope("rtnerf.composite"):
                tau = sigma * delta
                cum = jnp.cumsum(tau, axis=-1)
                t_local = jnp.exp(-(cum - tau))
                alpha = 1.0 - jnp.exp(-tau)
                w = t_local * alpha
                seg_rgb = jnp.sum(w[..., None] * rgb, axis=-2)  # (budget,3)
                seg_d = jnp.sum(w * ts, axis=-1)                # (budget,)
                seg_tau = jnp.where(sel, cum[..., -1], 0.0)     # (budget,)

            # scatter into the per-ray accumulators (pre-chunk T, exactly
            # the image path's chunk>1 approximation)
            with jax.named_scope("rtnerf.scatter"):
                t_here = jnp.exp(log_t)[ray_i]
                contrib = jnp.where(sel[:, None],
                                    t_here[:, None] * seg_rgb, 0.0)
                color = color.at[ray_i].add(contrib)
                depth = depth.at[ray_i].add(
                    jnp.where(sel, t_here * seg_d, 0.0))
                log_t = log_t.at[ray_i].add(-seg_tau)
                processed = processed + jnp.sum(s_mask.astype(jnp.int32))
                n_hit = jnp.sum(flat_hit.astype(jnp.int32))
                dropped = dropped + jnp.maximum(n_hit - budget, 0)
                pairs_max = jnp.maximum(pairs_max, n_hit)
                live = live + jnp.any(vld).astype(jnp.int32)
                hit_pairs = hit_pairs + jnp.minimum(n_hit, budget)
            return (log_t, color, depth, processed, dropped, pairs_max,
                    live, hit_pairs), None

        xs = (centers.reshape(n_chunks, chunk, 3),
              valid.reshape(n_chunks, chunk))
        zero = jnp.int32(0)
        init = (jnp.zeros((n_rays,), jnp.float32),
                jnp.zeros((n_rays, 3), jnp.float32),
                jnp.zeros((n_rays,), jnp.float32), zero, zero, zero, zero,
                zero)
        (log_t, color, depth, processed, dropped, pairs_max, live,
         hit_pairs), _ = jax.lax.scan(body, init, xs)
        t_final = jnp.exp(log_t)
        if white_bg:
            color = color + t_final[:, None]
        # depth is the opacity-weighted expected termination distance
        # (sum_k w_k t_k); opacity = 1 - T_final. The serving temporal tier
        # (serving/temporal.py) unprojects depth/opacity to forward-warp
        # this frame's radiance to the next camera.
        return color, {"t_final": t_final, "depth": depth,
                       "opacity": 1.0 - t_final,
                       "processed_samples": processed,
                       "dropped_pairs": dropped,
                       "active_pairs_max": pairs_max,
                       "live_steps": live, "hit_pairs": hit_pairs,
                       "scan_steps": jnp.int32(n_chunks),
                       "pair_slots": jnp.int32(n_chunks * budget),
                       "sample_slots": jnp.int32(n_chunks * budget * ns)}

    return render


def render_rtnerf(field, cfg: NeRFConfig, cubes: CubeSet, cam: Camera, *,
                  order_mode: str = "octant", chunk: int = 1,
                  intersect: str = "box",
                  white_bg: bool = True) -> Tuple[jax.Array, Dict]:
    """Full-image render via the RT-NeRF pipeline. Returns (rgb (H*W,3), stats).

    `field` is anything `field.as_backend` accepts: a DenseField / params
    dict evaluates the raw TensoRF factor arrays (baseline); a
    CompressedField evaluates the hybrid bitmap/COO-encoded factors (paper
    Sec. 4.2.2) — every grid read decodes the compressed stream in place,
    so the field's memory footprint in the hot loop is the encoded bytes.
    """
    f = field_lib.as_backend(field, cfg)
    factor_bytes = f.factor_bytes()
    factor_bytes_dense = f.dense_factor_bytes()
    tile = auto_tile(cfg, cam)
    perm = order_cubes(cubes, cam.origin, order_mode)
    centers = cubes.centers[perm]
    valid = cubes.valid[perm]
    n_pix = cam.h * cam.w
    delta = step_world(cfg)

    nc = centers.shape[0]
    n_chunks = nc // chunk

    def body(carry, xs):
        log_t, color, processed = carry
        ctr, vld = xs                                     # (chunk,3),(chunk,)

        with jax.named_scope("rtnerf.intersect"):
            def per_cube(c):
                return _cube_samples(cfg, cam, c, tile, intersect)
            pix_id, d, pts, ts, s_mask = jax.vmap(per_cube)(ctr)
            s_mask = s_mask & vld[:, None, None]
            P = pix_id.shape[1]

            # Sec. 3.2 early termination: skip points on rays already opaque
            t_here = jnp.exp(log_t.reshape(-1)[pix_id])   # (chunk,P)
            alive = t_here > cfg.term_eps
            s_mask = s_mask & alive[..., None]

            flat = pts.reshape(-1, 3)
            # points grouped by source cube for the fused streaming path
            cid = jnp.broadcast_to(
                jnp.arange(ctr.shape[0], dtype=jnp.int32)[:, None, None],
                s_mask.shape).reshape(-1)
        with jax.named_scope("rtnerf.field_eval"):
            sigma, feats = f.sigma_app(flat, ctr, cid)
            sigma = jnp.where(s_mask, sigma.reshape(s_mask.shape), 0.0)
            dirs = jnp.broadcast_to(d[:, :, None], pts.shape).reshape(-1, 3)
            rgb = f.color(feats, dirs).reshape(*s_mask.shape, 3)

        # per-(cube,pixel) local compositing along the segment
        tau = sigma * delta                               # (chunk,P,ns)
        cum = jnp.cumsum(tau, axis=-1)
        t_local = jnp.exp(-(cum - tau))
        alpha = 1.0 - jnp.exp(-tau)
        w = t_local * alpha
        seg_rgb = jnp.sum(w[..., None] * rgb, axis=-2)    # (chunk,P,3)
        seg_tau = cum[..., -1]                            # (chunk,P)

        # scatter into the running per-pixel (T, color) accumulators
        contrib = (t_here[..., None] * seg_rgb).reshape(-1, 3)
        ids = pix_id.reshape(-1)
        color = color.at[ids].add(contrib)
        log_t = log_t.at[ids].add(-seg_tau.reshape(-1))
        processed = processed + jnp.sum(s_mask.astype(jnp.float32))
        return (log_t, color, processed), None

    log_t0 = jnp.zeros((n_pix,), jnp.float32)
    color0 = jnp.zeros((n_pix, 3), jnp.float32)
    xs = (centers[: n_chunks * chunk].reshape(n_chunks, chunk, 3),
          valid[: n_chunks * chunk].reshape(n_chunks, chunk))
    (log_t, color, processed), _ = jax.lax.scan(body, (log_t0, color0,
                                                       jnp.float32(0)), xs)
    t_final = jnp.exp(log_t)
    if white_bg:
        color = color + t_final[:, None]

    ns = samples_per_segment(cfg)
    stats = {
        # the pipeline touches the occupancy structure once per non-zero cube
        "occ_accesses": jnp.asarray(float(cubes.count), jnp.float32),
        "candidate_samples": jnp.asarray(
            float(cubes.count) * tile * tile * ns, jnp.float32),
        "processed_samples": processed,
        "n_cubes": jnp.asarray(float(cubes.count), jnp.float32),
        "tile": jnp.asarray(float(tile), jnp.float32),
        # field-memory footprint of the hot loop (paper Sec. 4.2.2): the
        # bytes the factor reads stream from, in the active representation
        "factor_bytes": jnp.asarray(float(factor_bytes), jnp.float32),
        "factor_bytes_dense": jnp.asarray(float(factor_bytes_dense),
                                          jnp.float32),
    }
    return color, stats
