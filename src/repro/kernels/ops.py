"""jit'd public wrappers for the Pallas kernels with platform dispatch.

The NeRF kernels run only when forced: the v5e compiler refuses them
(docs/kernels.md support matrix), so their jnp twins serve by default on
every backend. `flash_attention` runs its kernel by default on TPU. A
kernel forced with `force="pallas"` runs in interpret mode off-TPU (for
correctness, not speed) and compiled on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import fused_sample, ref
from repro.kernels.bitmap_decode import bitmap_gather as _bitmap_gather_pallas
from repro.kernels.bitmap_decode import bitmap_matmul as _bitmap_pallas
from repro.kernels.coo_gather import coo_gather as _coo_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.volume_render import volume_render as _vr_pallas


def _mode(force: Optional[str]) -> str:
    if force:
        return force
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("cols", "force"))
def bitmap_matmul(words, rowptr, values, x, *, cols: int,
                  force: Optional[str] = None):
    if (force or "ref") == "ref":
        return ref.bitmap_decode_matmul_ref(words, rowptr, values, x, cols)
    return _bitmap_pallas(words, rowptr, values, x, cols=cols,
                          interpret=(jax.default_backend() != "tpu"))


@functools.partial(jax.jit, static_argnames=("cols", "force"))
def bitmap_gather(words, rowptr, values, queries, *, cols: int,
                  force: Optional[str] = None):
    if (force or "ref") == "ref":
        return ref.bitmap_gather_ref(words, rowptr, values, queries, cols)
    return _bitmap_gather_pallas(words, rowptr, values, queries, cols=cols,
                                 interpret=(jax.default_backend() != "tpu"))


@functools.partial(jax.jit, static_argnames=("force",))
def coo_gather(coords, values, queries, *, force: Optional[str] = None):
    if (force or "ref") == "ref":
        return ref.coo_gather_ref(coords, values, queries)
    return _coo_pallas(coords, values, queries,
                       interpret=(jax.default_backend() != "tpu"))


def fused_mode(force: Optional[str] = None) -> str:
    """Dispatch mode for the fused decode-sample-accumulate path: "fused"
    (Pallas kernel; interpret off-TPU), "fused_ref" (jnp twin, the serving
    default on every backend — the v5e compiler refuses the kernel), or
    whatever explicit mode `force` names ("per-op" makes core/tensorf fall
    back to the per-op gather composition). The per-op force vocabulary
    maps onto its fused equivalents so callers can use one force string
    for the whole hybrid eval."""
    if force in ("pallas", "fused"):
        return "fused"
    if force:
        return "fused_ref" if force == "ref" else force
    return "fused_ref"


fused_supported = fused_sample.fused_supported


@functools.partial(jax.jit, static_argnames=(
    "spec", "grid_res", "scene_bound", "window", "app_dim", "force"))
def fused_sigma_app(spec, streams, basis, pts, cube_base, cube_id, *,
                    grid_res: int, scene_bound: float, window: int,
                    app_dim: int, force: Optional[str] = None):
    """(sigma_raw, feat) straight from the encoded factor streams — the
    fused decode-sample-accumulate kernel (kernels/fused_sample.py). `spec`
    is the static factor-structure tuple from tensorf.fused_field_inputs;
    it participates in the jit key, so hot-swapped fields with the same
    encoded structure reuse the compiled step."""
    m = fused_mode(force)
    if m == "fused_ref":
        return fused_sample.fused_sigma_app_ref(
            spec, streams, basis, pts, cube_base, cube_id,
            grid_res=grid_res, scene_bound=scene_bound, window=window,
            app_dim=app_dim)
    return fused_sample.fused_sigma_app(
        spec, streams, basis, pts, cube_base, cube_id,
        grid_res=grid_res, scene_bound=scene_bound, window=window,
        app_dim=app_dim, interpret=(jax.default_backend() != "tpu"))


@functools.partial(jax.jit, static_argnames=("delta", "term_eps", "force"))
def volume_render(sigma, rgb, *, delta: float, term_eps: float = 1e-4,
                  force: Optional[str] = None):
    if (force or "ref") == "ref":
        return ref.volume_render_ref(sigma, rgb, delta, term_eps)
    return _vr_pallas(sigma, rgb, delta=delta, term_eps=term_eps,
                      interpret=(jax.default_backend() != "tpu"))


@functools.partial(jax.jit, static_argnames=("causal", "force"))
def flash_attention(q, k, v, *, causal: bool = True,
                    force: Optional[str] = None):
    m = _mode(force)
    if m == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(q, k, v, causal=causal,
                         interpret=(jax.default_backend() != "tpu"))
