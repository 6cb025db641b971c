"""Pallas TPU kernel: bitmap-encoded sparse matmul y = W @ x (paper H2,
"high-density sparse search unit", TPU-native form — DESIGN.md §3).

HBM holds only the *compressed* stream (uint32 bitmap words + row pointers +
packed non-zeros). Each grid step DMAs one row-block into VMEM, reconstructs
the dense row-block with a vectorised prefix-popcount (the ASIC's fixed
3-cycle search becomes a fixed per-tile decode), and feeds the MXU. The
memory-roofline win is the compression ratio; compute stays dense.

The packed-value expansion is a dynamic VMEM gather — supported in interpret
mode (our validation target); the TPU v5e compiler refuses both kernels
here (docs/kernels.md support matrix). The oracle is
ref.bitmap_decode_matmul_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 8
DEFAULT_BLOCK_Q = 512


def _kernel(words_ref, rowptr_ref, values_ref, x_ref, y_ref, *, cols: int):
    words = words_ref[...]                          # (BR, cols//32) uint32
    br = words.shape[0]
    rep = jnp.repeat(words, 32, axis=1)[:, :cols]   # static expand
    shift = (jnp.arange(cols, dtype=jnp.uint32) % 32)[None, :]
    bits = ((rep >> shift) & jnp.uint32(1)).astype(jnp.int32)   # (BR, cols)
    prefix = jnp.cumsum(bits, axis=1) - bits        # nnz before (r, c)
    addr = rowptr_ref[...][:, None] + prefix
    nv = values_ref.shape[0]
    vals = jnp.take(values_ref[...], jnp.clip(addr, 0, nv - 1).reshape(-1)
                    ).reshape(br, cols)
    w = jnp.where(bits > 0, vals, 0).astype(x_ref.dtype)
    y_ref[...] = jnp.dot(w, x_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(y_ref.dtype)


def _gather_kernel(words_ref, rowptr_ref, values_ref, q_ref, out_ref, *,
                   cols: int):
    """Random-access block: per query lane, bit test + prefix-popcount over
    the query row's bitmap words (the ASIC's fixed 3-cycle search)."""
    words = words_ref[...]                           # (rows, W) in VMEM
    q = q_ref[...]
    r = q // cols
    c = q % cols
    wi = (c // 32).astype(jnp.int32)
    bi = (c % 32).astype(jnp.uint32)
    qwords = jnp.take(words, r, axis=0)              # (BQ, W)
    widx = jnp.arange(words.shape[1], dtype=jnp.int32)[None, :]
    below = jnp.left_shift(jnp.uint32(1), bi) - jnp.uint32(1)
    mask = jnp.where(widx < wi[:, None], jnp.uint32(0xFFFFFFFF),
                     jnp.where(widx == wi[:, None], below[:, None],
                               jnp.uint32(0)))
    prefix = jnp.sum(jax.lax.population_count(qwords & mask), axis=1)
    word_at = jnp.take(words.reshape(-1), r * words.shape[1] + wi)
    bit = (word_at >> bi) & jnp.uint32(1)
    addr = jnp.take(rowptr_ref[...], r) + prefix.astype(jnp.int32)
    nv = values_ref.shape[0]
    vals = jnp.take(values_ref[...], jnp.clip(addr, 0, nv - 1))
    out_ref[...] = jnp.where(bit > 0, vals, 0).astype(out_ref.dtype)


def bitmap_gather(words: jax.Array, rowptr: jax.Array, values: jax.Array,
                  queries: jax.Array, *, cols: int,
                  block_q: int = DEFAULT_BLOCK_Q,
                  interpret: bool = True) -> jax.Array:
    """values of the encoded matrix at linear indices `queries` (0 at zeros).

    The whole compressed stream (bitmap words + rowptr + packed values) sits
    in VMEM; each grid step serves one query block. Interpret mode is the
    CPU validation target; the oracle is ref.bitmap_gather_ref.
    """
    nq = queries.shape[0]
    bq = min(block_q, nq)
    assert nq % bq == 0, (nq, bq)
    return pl.pallas_call(
        functools.partial(_gather_kernel, cols=cols),
        grid=(nq // bq,),
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0, 0)),
            pl.BlockSpec((rowptr.shape[0],), lambda i: (0,)),
            pl.BlockSpec((values.shape[0],), lambda i: (0,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nq,), values.dtype),
        interpret=interpret,
    )(words, rowptr, values, queries)


def bitmap_matmul(words: jax.Array, rowptr: jax.Array, values: jax.Array,
                  x: jax.Array, *, cols: int,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = True) -> jax.Array:
    """y = decode(words, rowptr, values) @ x. x (cols, n)."""
    rows = words.shape[0]
    w32 = words.shape[1]
    n = x.shape[1]
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, cols=cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w32), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((values.shape[0],), lambda i: (0,)),
            pl.BlockSpec((cols, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret,
    )(words, rowptr, values, x)
