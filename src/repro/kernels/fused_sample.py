"""Fused decode-sample-accumulate Pallas kernel for the hybrid field
(Potamoi's unified-streaming insight applied to the paper's H1 codec).

One kernel replaces the per-op gather pipeline of the hybrid eval path
(`bitmap_gather` / `coo_gather` called once per factor per stencil batch):
it takes the *encoded* bitmap/COO streams of all twelve TensoRF VM factor
slices, and per block of ray points

  1. **decode** — reconstructs, in VMEM, the small per-cube factor windows
     the block's points can touch: bitmap entries via the O(1) rank-table
     lookup (one rank read + one masked-word popcount, the ASIC's
     fixed-latency search), COO entries via branchless binary search over
     the sorted coordinate stream;
  2. **sample** — interpolates the factored VM grids at the points
     (bilinear on plane windows, linear on line windows), reading only the
     decoded windows;
  3. **accumulate** — folds the Eq. 2 products into the density sum and the
     basis-projected appearance features in place.

No dense factor is ever written back to HBM: the working set per grid step
is the encoded streams plus `C * R * W * W` floats of decoded windows
(C = cubes in flight, W = window span — a few KB), which is the whole
point of streaming the compressed representation.

Layout contract (shared with `core/tensorf.fused_field_inputs` and
`kernels/ops.fused_sigma_app`):

  * `spec` is a flat tuple of 12 factor specs in canonical order —
    sigma_planes[0..2], sigma_lines[0..2], app_planes[0..2],
    app_lines[0..2] — each `(fmt, rows, ncols)` with fmt in
    {"dense", "bitmap", "coo"}. It is static (hashable) and participates in
    jit keys, so a hot-swapped field with the same encoded structure reuses
    the compiled kernel.
  * `streams` is the matching flat tuple of arrays: dense -> (matrix,),
    bitmap -> (words, rank, values) (rank from `core/sparse.bitmap_rank`),
    coo -> (coords, values).
  * Points are grouped by occupancy cube: `cube_base` (C, 3) holds each
    cube's window origin in grid coords, `cube_id` (N,) maps every point to
    its cube. Callers guarantee every *unmasked* point's interpolation
    stencil falls inside its cube's window (`core/tensorf.window_base` /
    `fused_window`); out-of-window points read clipped window entries and
    must be masked out downstream (the render paths multiply them by zero).

Interpret mode is the validated CI target (tests/test_kernels.py fused
parity suite). The TPU v5e compiler refuses the kernel (Mosaic's gather
lowering rejects the in-kernel `jnp.take`; tests/test_tpu_compile.py), so
the pure-jnp twin `fused_sigma_app_ref` is the serving path on every
backend (dispatched by kernels/ops.py) as well as the parity oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_PTS = 1024

# mode m pairs plane axes with line axis (mirrors core/tensorf.py — the
# kernels layer sits below core, so the constant is restated, not imported)
PLANE_AXES = ((1, 2), (0, 2), (0, 1))
LINE_AXES = (0, 1, 2)

STREAMS_PER_FMT = {"dense": 1, "bitmap": 3, "coo": 2}


def fused_supported(spec) -> bool:
    """Whether the fused kernel can serve this field structure. False sends
    the whole eval down the per-op oracle composition in core/tensorf.py
    (the dispatch contract's per-op fallback)."""
    return (len(spec) == 12
            and all(fs[0] in STREAMS_PER_FMT for fs in spec))


def stream_count(spec) -> int:
    return sum(STREAMS_PER_FMT[fs[0]] for fs in spec)


def group_streams(spec, streams):
    """Pair each factor spec with its slice of the flat stream tuple."""
    out, i = [], 0
    for fs in spec:
        k = STREAMS_PER_FMT[fs[0]]
        out.append((fs, tuple(streams[i:i + k])))
        i += k
    if i != len(streams):
        raise ValueError(f"got {len(streams)} stream arrays, spec needs {i}")
    return out


def to_grid(pts, *, grid_res: int, scene_bound: float):
    """World [-bound, bound]^3 -> continuous grid coords [0, G-1] (the same
    mapping as core/tensorf.to_grid, restated for layering)."""
    return (pts / scene_bound * 0.5 + 0.5) * (grid_res - 1)


def _decode_cols(fs, arrs, cols, *, searchsorted: bool):
    """All R rows of one encoded (R, ncols) factor at column indices `cols`
    (K,) -> (R, K), decoded straight from the stream (VMEM when called from
    the kernel body). This is the per-element form of the H1 codec: bitmap
    = rank lookup + single-word popcount, COO = binary search, dense = read.
    """
    fmt, rows, ncols = fs
    if fmt == "dense":
        return jnp.take(arrs[0], cols, axis=1)
    if fmt == "bitmap":
        words, rank, values = arrs
        wi = (cols // 32).astype(jnp.int32)
        bi = (cols % 32).astype(jnp.uint32)
        w = jnp.take(words, wi, axis=1)                      # (R, K)
        rk = jnp.take(rank, wi, axis=1)                      # (R, K)
        below = (jnp.left_shift(jnp.uint32(1), bi)
                 - jnp.uint32(1))[None, :]
        addr = rk + jax.lax.population_count(w & below).astype(jnp.int32)
        bit = (w >> bi[None, :]) & jnp.uint32(1)
        nv = values.shape[0]
        vals = jnp.take(values, jnp.clip(addr, 0, nv - 1).reshape(-1)
                        ).reshape(addr.shape)
        return jnp.where(bit > 0, vals, 0).astype(values.dtype)
    coords, values = arrs                                    # fmt == "coo"
    q = (jnp.arange(rows, dtype=jnp.int32)[:, None] * ncols
         + cols[None, :].astype(jnp.int32))                  # (R, K)
    n = coords.shape[0]
    if searchsorted:                                         # jnp oracle
        lo = jnp.searchsorted(coords, q.reshape(-1)).reshape(
            q.shape).astype(jnp.int32)
    else:                                       # in-kernel: static unroll
        steps = max(int(math.ceil(math.log2(n))), 1) + 1     # lo == hi
        lo = jnp.zeros(q.shape, jnp.int32)
        hi = jnp.full(q.shape, n, jnp.int32)
        for _ in range(steps):
            mid = (lo + hi) // 2
            cm = jnp.take(coords, jnp.clip(mid, 0, n - 1).reshape(-1)
                          ).reshape(mid.shape)
            go_right = cm < q
            lo = jnp.where(go_right, mid + 1, lo)
            hi = jnp.where(go_right, hi, mid)
    safe = jnp.clip(lo, 0, n - 1)
    found = (lo < n) & (jnp.take(coords, safe.reshape(-1)
                                 ).reshape(safe.shape) == q)
    vals = jnp.take(values, safe.reshape(-1)).reshape(safe.shape)
    return jnp.where(found, vals, 0).astype(values.dtype)


def _eval(groups, basis, ptsg, base, cid, *, grid_res: int, window: int,
          app_dim: int, searchsorted: bool):
    """The shared decode-sample-accumulate math (jnp oracle and kernel body
    run this same function; only the COO search strategy differs).

    groups: the 12 (spec, arrays) entries in canonical order; ptsg (N, 3)
    continuous grid coords; base (C, 3) int32 window origins; cid (N,)
    int32 cube ids. Returns (sigma_raw (N,), feat (N, app_dim)) — raw
    pre-softplus density sum and basis-projected appearance features.
    """
    G, W = grid_res, window
    C = base.shape[0]
    n = ptsg.shape[0]
    ii = jnp.arange(W, dtype=jnp.int32)

    # jax.named_scope markers (trace-time only, zero runtime cost) tag the
    # decode / sample / accumulate phases in the HLO so XLA profiler
    # captures line up with the serving spans (docs/observability.md)
    # per-point global stencil — identical arithmetic to the dense path:
    # clip to the grid, floor to the low corner, fractional weights; then
    # shift into window-local coords (clipped: out-of-window points are
    # masked to zero contribution by every caller)
    p = jnp.clip(ptsg, 0.0, G - 1.0)
    p0 = jnp.clip(jnp.floor(p).astype(jnp.int32), 0, G - 2)
    fr = p - p0
    loc = jnp.clip(p0 - jnp.take(base, cid, axis=0), 0, W - 2)   # (N, 3)

    out = jnp.zeros((n, 1 + app_dim), jnp.float32)       # [sigma | feat]
    for m in range(3):
        a, b = PLANE_AXES[m]
        ax = LINE_AXES[m]
        spf, spa = groups[m]            # sigma plane / line, mode m
        slf, sla = groups[3 + m]
        apf, apa = groups[6 + m]        # app plane / line, mode m
        alf, ala = groups[9 + m]
        Rs, Rc = spf[1], apf[1]

        # 1. decode — per-cube factor windows, straight from the encoded
        # streams (bitmap rank lookup / COO binary search). The sigma and
        # app windows of one mode share the same stencil, so they are
        # decoded into ONE (Rs+Rc, ...) block and sampled together —
        # halving the gather count versus evaluating the heads separately
        # (the structural win over the dense two-head baseline).
        with jax.named_scope(f"fused.decode.m{m}"):
            pcols = ((base[:, a, None, None] + ii[None, :, None]) * G
                     + base[:, b, None, None]
                     + ii[None, None, :]).reshape(-1)
            pw = jnp.concatenate([
                _decode_cols(spf, spa, pcols, searchsorted=searchsorted),
                _decode_cols(apf, apa, pcols, searchsorted=searchsorted),
            ]).T                                         # (C*W*W, Rs+Rc)
            lcols = (base[:, ax, None] + ii[None, :]).reshape(-1)
            lw = jnp.concatenate([
                _decode_cols(slf, sla, lcols, searchsorted=searchsorted),
                _decode_cols(alf, ala, lcols, searchsorted=searchsorted),
            ]).T                                         # (C*W, Rs+Rc)

        # 2. sample — bilinear on the plane window, linear on the line.
        # Windows are transposed to (cells, R) BEFORE the gathers so each
        # of the N stencil reads pulls one contiguous R-length row —
        # row-gathers on the small window are the cheap orientation;
        # column-gathers (stride R) measured ~5x slower on CPU.
        with jax.named_scope(f"fused.sample.m{m}"):
            lu, lv, lx = loc[:, a], loc[:, b], loc[:, ax]
            fu = fr[:, a, None]
            fv = fr[:, b, None]
            fx = fr[:, ax, None]
            i00 = (cid * W + lu) * W + lv
            p00 = jnp.take(pw, i00, axis=0)              # (N, Rs+Rc)
            p01 = jnp.take(pw, i00 + 1, axis=0)
            p10 = jnp.take(pw, i00 + W, axis=0)
            p11 = jnp.take(pw, i00 + W + 1, axis=0)
            pm = (p00 * (1 - fu) * (1 - fv) + p01 * (1 - fu) * fv
                  + p10 * fu * (1 - fv) + p11 * fu * fv)
            il = cid * W + lx
            lm = (jnp.take(lw, il, axis=0) * (1 - fx)
                  + jnp.take(lw, il + 1, axis=0) * fx)
            comp = pm * lm                               # (N, Rs+Rc)

        # 3. accumulate — ONE matmul folds both heads: the basis slice is
        # extended with a leading ones-column over the sigma rows, so
        # out[:, 0] accumulates the density sum and out[:, 1:] the
        # basis-projected features. Slicing comp into two consumers
        # instead (sum + matmul) makes XLA CPU re-evaluate the whole
        # gather fusion per consumer — measured 6x slower.
        with jax.named_scope(f"fused.accumulate.m{m}"):
            bm = basis[m * Rc:(m + 1) * Rc]              # (Rc, app_dim)
            bext = jnp.concatenate([
                jnp.concatenate(
                    [jnp.ones((Rs, 1), jnp.float32),
                     jnp.zeros((Rs, app_dim), jnp.float32)], axis=1),
                jnp.concatenate(
                    [jnp.zeros((Rc, 1), jnp.float32), bm], axis=1),
            ])                                           # (Rs+Rc, 1+app_dim)
            out = out + jnp.dot(comp, bext,
                                preferred_element_type=jnp.float32)
    return out[:, 0], out[:, 1:]


def fused_sigma_app_ref(spec, streams, basis, pts, cube_base, cube_id, *,
                        grid_res: int, scene_bound: float, window: int,
                        app_dim: int):
    """Pure-jnp twin of the fused kernel: same windows-then-sample math,
    vectorised with plain jnp (COO decode via `searchsorted`). This is both
    the parity oracle for the Pallas kernel and the CPU serving fast path —
    kernels/ops.py dispatches here when the backend is not a TPU."""
    groups = group_streams(spec, streams)
    ptsg = to_grid(pts, grid_res=grid_res, scene_bound=scene_bound)
    return _eval(groups, basis, ptsg, jnp.asarray(cube_base, jnp.int32),
                 jnp.asarray(cube_id, jnp.int32), grid_res=grid_res,
                 window=window, app_dim=app_dim, searchsorted=True)


def _kernel(*refs, spec, n_streams: int, grid_res: int, scene_bound: float,
            window: int, app_dim: int):
    pts_ref, cid_ref, base_ref, basis_ref = refs[:4]
    stream_refs = refs[4:4 + n_streams]
    out_sig_ref, out_feat_ref = refs[4 + n_streams:]
    arrays = tuple(r[...] for r in stream_refs)          # streams in VMEM
    groups = group_streams(spec, arrays)
    ptsg = to_grid(pts_ref[...], grid_res=grid_res, scene_bound=scene_bound)
    sig, feat = _eval(groups, basis_ref[...], ptsg, base_ref[...],
                      cid_ref[...], grid_res=grid_res, window=window,
                      app_dim=app_dim, searchsorted=False)
    out_sig_ref[...] = sig
    out_feat_ref[...] = feat.astype(out_feat_ref.dtype)


def _full(shape):
    """BlockSpec for an array that sits whole in VMEM on every grid step."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def fused_sigma_app(spec, streams, basis, pts, cube_base, cube_id, *,
                    grid_res: int, scene_bound: float, window: int,
                    app_dim: int, block_pts: int = DEFAULT_BLOCK_PTS,
                    interpret: bool = True):
    """(sigma_raw (N,), feat (N, app_dim)) for points `pts` (N, 3) grouped
    by cube, evaluated straight from the encoded factor streams.

    Grid is over point blocks; every step holds the full encoded streams in
    VMEM and re-decodes the (small) cube windows — decode cost is
    C*W*W*sum(R) lookups per step, negligible against sampling. (A scratch
    buffer persisting windows across steps would remove even that; left for
    a later PR.) Wrapper pads N to a block multiple and slices the pad off.
    """
    n = pts.shape[0]
    bp = min(block_pts, max(n, 1))
    pad = (-n) % bp
    cube_id = jnp.asarray(cube_id, jnp.int32)
    cube_base = jnp.asarray(cube_base, jnp.int32)
    if pad:
        pts = jnp.concatenate([pts, jnp.zeros((pad, 3), pts.dtype)])
        cube_id = jnp.concatenate([cube_id, jnp.zeros((pad,), jnp.int32)])
    npad = n + pad
    in_specs = ([pl.BlockSpec((bp, 3), lambda i: (i, 0)),
                 pl.BlockSpec((bp,), lambda i: (i,)),
                 _full(cube_base.shape),
                 _full(basis.shape)]
                + [_full(s.shape) for s in streams])
    sig, feat = pl.pallas_call(
        functools.partial(_kernel, spec=spec, n_streams=len(streams),
                          grid_res=grid_res, scene_bound=scene_bound,
                          window=window, app_dim=app_dim),
        grid=(npad // bp,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bp,), lambda i: (i,)),
                   pl.BlockSpec((bp, app_dim), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((npad,), jnp.float32),
                   jax.ShapeDtypeStruct((npad, app_dim), jnp.float32)],
        interpret=interpret,
    )(pts, cube_id, cube_base, basis, *streams)
    return sig[:n], feat[:n]
