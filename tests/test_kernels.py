"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle,
plus hypothesis property tests on randomly-sparse inputs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, strategies as st

from repro.configs.rtnerf import demo_config
from repro.core import field as field_lib
from repro.core import sparse, tensorf
from repro.kernels import fused_sample, ops, ref
from repro.kernels.bitmap_decode import bitmap_gather, bitmap_matmul
from repro.kernels.coo_gather import coo_gather
from repro.kernels.flash_attention import flash_attention
from repro.kernels.volume_render import volume_render


# ---------------------------------------------------------------- bitmap ---
@pytest.mark.parametrize("rows,cols,n", [(8, 32, 4), (16, 64, 8), (32, 128, 1),
                                         (8, 96, 16)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("density", [0.05, 0.5, 1.0])
def test_bitmap_matmul_sweep(rows, cols, n, dtype, density):
    rng = np.random.RandomState(rows * cols + n)
    w = rng.randn(rows, cols).astype(dtype)
    w[rng.rand(rows, cols) >= density] = 0
    enc = sparse.encode_bitmap(w)
    x = rng.randn(cols, n).astype(dtype)
    y_pal = bitmap_matmul(enc.words, enc.rowptr, enc.values, jnp.asarray(x),
                          cols=cols, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32), w @ x,
                               rtol=2e-2, atol=2e-2)


def test_bitmap_all_zero():
    w = np.zeros((8, 32), np.float32)
    enc = sparse.encode_bitmap(w)
    x = np.ones((32, 2), np.float32)
    y = bitmap_matmul(enc.words, enc.rowptr, enc.values, jnp.asarray(x),
                      cols=32, interpret=True)
    assert np.all(np.asarray(y) == 0)


@pytest.mark.parametrize("rows,cols,nq", [(8, 32, 128), (16, 96, 512),
                                          (40, 70, 256)])
@pytest.mark.parametrize("density", [0.0, 0.1, 0.5, 1.0])
def test_bitmap_gather_sweep(rows, cols, nq, density):
    """Pallas bitmap random-access (interpret) vs jnp oracle vs dense."""
    rng = np.random.RandomState(rows + cols + nq)
    w = rng.randn(rows, cols).astype(np.float32)
    w[rng.rand(rows, cols) >= density] = 0
    enc = sparse.encode_bitmap(w)
    q = jnp.asarray(rng.randint(0, rows * cols, nq), jnp.int32)
    got_pal = bitmap_gather(enc.words, enc.rowptr, enc.values, q,
                            cols=cols, interpret=True)
    got_ref = ref.bitmap_gather_ref(enc.words, enc.rowptr, enc.values, q,
                                    cols)
    want = w.reshape(-1)[np.asarray(q)]
    np.testing.assert_array_equal(np.asarray(got_pal), want)
    np.testing.assert_array_equal(np.asarray(got_ref), want)


def test_bitmap_gather_empty_rows():
    w = np.zeros((8, 64), np.float32)
    w[3, 10] = 2.5
    w[6, 63] = -1.0
    enc = sparse.encode_bitmap(w)
    q = jnp.arange(8 * 64, dtype=jnp.int32)
    got = bitmap_gather(enc.words, enc.rowptr, enc.values, q, cols=64,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got).reshape(8, 64), w)


def test_ops_bitmap_gather_ref_dispatch():
    rng = np.random.RandomState(5)
    w = rng.randn(8, 32).astype(np.float32)
    w[rng.rand(8, 32) < 0.6] = 0
    enc = sparse.encode_bitmap(w)
    q = jnp.asarray(rng.randint(0, 8 * 32, 64), jnp.int32)
    got = ops.bitmap_gather(enc.words, enc.rowptr, enc.values, q, cols=32)
    np.testing.assert_array_equal(np.asarray(got),
                                  w.reshape(-1)[np.asarray(q)])


# ------------------------------------------------------------------- coo ---
@pytest.mark.parametrize("size,nq", [(64, 128), (1000, 512), (5, 128)])
def test_coo_gather_sweep(size, nq):
    rng = np.random.RandomState(size)
    flat = rng.randn(size).astype(np.float32)
    flat[rng.rand(size) < 0.9] = 0
    enc = sparse.encode_coo(flat.reshape(1, -1))
    q = jnp.asarray(rng.randint(0, size, nq), jnp.int32)
    got = coo_gather(enc.coords, enc.values, q, interpret=True)
    np.testing.assert_allclose(np.asarray(got), flat[np.asarray(q)])


@given(st.integers(16, 200), st.floats(0.5, 1.0), st.integers(0, 10_000))
def test_coo_gather_property(size, sparsity, seed):
    rng = np.random.RandomState(seed)
    flat = rng.randn(size).astype(np.float32)
    flat[rng.rand(size) < sparsity] = 0
    enc = sparse.encode_coo(flat.reshape(1, -1))
    q = jnp.asarray(rng.randint(0, size, 128), jnp.int32)
    got = ref.coo_gather_ref(enc.coords, enc.values, q)
    np.testing.assert_allclose(np.asarray(got), flat[np.asarray(q)])


# --------------------------------------------------------- volume render ---
@pytest.mark.parametrize("r,n", [(128, 64), (256, 128), (128, 192)])
@pytest.mark.parametrize("scale", [0.1, 3.0, 50.0])
def test_volume_render_sweep(r, n, scale):
    rng = np.random.RandomState(r + n)
    sigma = jnp.asarray(np.abs(rng.randn(r, n)).astype(np.float32) * scale)
    rgb = jnp.asarray(rng.rand(r, n, 3).astype(np.float32))
    c1, t1, n1 = ref.volume_render_ref(sigma, rgb, 0.02, 1e-4)
    c2, t2, n2 = volume_render(sigma, rgb, delta=0.02, term_eps=1e-4,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), atol=1e-6)
    assert float(n1) == float(n2)


def test_volume_render_early_termination_counts():
    # opaque wall at sample 2: nearly everything after it should be skipped
    sigma = jnp.zeros((64, 64), jnp.float32).at[:, 2].set(1e4)
    rgb = jnp.ones((64, 64, 3), jnp.float32) * 0.5
    c, t, nproc = ref.volume_render_ref(sigma, rgb, 0.1, 1e-4)
    assert float(nproc) <= 64 * 4          # only the first few samples
    np.testing.assert_allclose(np.asarray(t), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c), 0.5, atol=1e-4)


def test_volume_render_transmittance_invariants():
    rng = np.random.RandomState(0)
    sigma = jnp.asarray(np.abs(rng.randn(32, 32)).astype(np.float32))
    rgb = jnp.asarray(rng.rand(32, 32, 3).astype(np.float32))
    c, t, _ = ref.volume_render_ref(sigma, rgb, 0.05, 1e-4)
    assert np.all(np.asarray(t) >= 0) and np.all(np.asarray(t) <= 1)
    # colors bounded by max rgb (convex-ish combination + leftover T)
    assert np.all(np.asarray(c) <= 1.0 + 1e-5)


# ----------------------------------------------------------------- flash ---
@pytest.mark.parametrize("b,h,s,d", [(1, 2, 128, 64), (2, 4, 256, 64),
                                     (1, 1, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, s, d, causal):
    rng = np.random.RandomState(b * s + d)
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
    o_pal = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    o_ref = ref.flash_attention_ref(q, k, v)
    o_pal = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


# ------------------------------------------------- fused decode-sample ---
def _fused_case(sparsity_lvl, threshold, seed=0, zero_slices=False):
    """A tiny encoded field + cube-grouped query points for fused parity
    tests. Returns (cfg, cf, centers, cube_id, pts)."""
    cfg = demo_config(tiny=True)
    params = tensorf.init_field(cfg, jax.random.PRNGKey(seed))
    params = tensorf.prune_to_sparsity(params, sparsity_lvl)
    if zero_slices:                       # whole factor modes with nnz == 0
        params["sigma_planes"] = params["sigma_planes"].at[1].set(0.0)
        params["app_lines"] = params["app_lines"].at[2].set(0.0)
    cf = field_lib.DenseField(params, cfg).encode(threshold)
    rng = np.random.RandomState(seed)
    C = 4
    ci = rng.randint(0, cfg.cube_grid_res, size=(C, 3))
    centers = jnp.asarray(
        -cfg.scene_bound + (ci + 0.5) * cfg.cube_world(), jnp.float32)
    cid = jnp.asarray(rng.randint(0, C, 300), jnp.int32)
    half = cfg.cube_world() / 2.0
    off = jnp.asarray(rng.uniform(-half, half, (300, 3)), jnp.float32)
    pts = jnp.take(centers, cid, axis=0) + off
    return cfg, cf, centers, cid, pts


def _fused_eval(cfg, cf, centers, cid, pts, force):
    base = tensorf.window_base(cfg, centers)
    return tensorf.eval_sigma_app_hybrid(cf, cfg, pts, base, cid,
                                         force=force)


@pytest.mark.parametrize("force", ["fused_ref", "fused"])
@pytest.mark.parametrize("case,want_fmts", [
    ("bitmap", {"bitmap"}),               # below-threshold factors -> bitmap
    ("coo", {"coo"}),                     # at/above threshold -> COO
    ("mixed", {"bitmap", "coo"}),         # both formats in one field
    ("empty", {"coo"}),                   # factor modes with zero nnz
])
def test_fused_parity(case, want_fmts, force):
    """Fused streaming kernel (jnp oracle AND Pallas interpret mode) vs the
    per-op gather composition, across the codec's format space."""
    if case == "bitmap":
        cfg, cf, centers, cid, pts = _fused_case(0.6, threshold=0.99)
    elif case == "coo":
        cfg, cf, centers, cid, pts = _fused_case(0.9, threshold=0.80)
    elif case == "empty":
        cfg, cf, centers, cid, pts = _fused_case(0.9, threshold=0.80,
                                                 zero_slices=True)
    else:                                 # mixed: splice the two encodings
        cfg, bm, centers, cid, pts = _fused_case(0.6, threshold=0.99)
        co = bm.decode().encode(0.0)
        cf = field_lib.CompressedField(
            {"sigma_planes": bm.factors["sigma_planes"],
             "sigma_lines": co.factors["sigma_lines"],
             "app_planes": co.factors["app_planes"],
             "app_lines": bm.factors["app_lines"]},
            bm.extras, cfg, bm.threshold)
    fmts = {ef.fmt for efs in cf.factors.values() for ef in efs}
    assert fmts == want_fmts, f"case {case} encoded as {fmts}"
    want_sig = cf.sigma(pts)              # per-op oracle composition
    want_feat = cf.app_features(pts)
    got_sig, got_feat = _fused_eval(cfg, cf, centers, cid, pts, force)
    np.testing.assert_allclose(np.asarray(got_sig), np.asarray(want_sig),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_feat), np.asarray(want_feat),
                               rtol=1e-5, atol=1e-5)


def test_fused_multi_block_padding():
    """Point counts that are not a multiple of the kernel block exercise the
    pad-and-slice wrapper and a multi-step Pallas grid."""
    cfg, cf, centers, cid, pts = _fused_case(0.9, threshold=0.80)
    spec, streams = tensorf.fused_field_inputs(cf)
    base = tensorf.window_base(cfg, centers)
    W = tensorf.fused_window(cfg)
    want, _ = fused_sample.fused_sigma_app_ref(
        spec, streams, cf.extras["basis"], pts, base, cid,
        grid_res=cfg.grid_res, scene_bound=cfg.scene_bound, window=W,
        app_dim=cfg.app_dim)
    got, _ = fused_sample.fused_sigma_app(
        spec, streams, cf.extras["basis"], pts, base, cid,
        grid_res=cfg.grid_res, scene_bound=cfg.scene_bound, window=W,
        app_dim=cfg.app_dim, block_pts=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fused_out_of_window_points_are_finite():
    """Points outside their cube's window read clipped entries by contract
    (callers mask them); the kernel must stay in-bounds and finite."""
    cfg, cf, centers, cid, pts = _fused_case(0.9, threshold=0.80)
    far = pts + 10.0 * cfg.cube_world()   # well outside every window
    sig, feat = _fused_eval(cfg, cf, centers, cid, far, "fused_ref")
    assert np.all(np.isfinite(np.asarray(sig)))
    assert np.all(np.isfinite(np.asarray(feat)))


def test_fused_dispatch_contract():
    """ops.fused_mode / hybrid_dispatch: fused_ref by default on every
    backend (the v5e compiler refuses the kernel), "per-op" forces the
    gather composition, unsupported specs fall back."""
    cfg, cf, centers, cid, pts = _fused_case(0.9, threshold=0.80)
    assert ops.fused_mode("pallas") == "fused"
    assert ops.fused_mode("ref") == "fused_ref"
    assert ops.fused_mode("per-op") == "per-op"
    assert ops.fused_mode() == "fused_ref"
    assert tensorf.hybrid_dispatch(cf) == "fused_ref"
    spec, _ = tensorf.fused_field_inputs(cf)
    assert len(spec) == 12 and fused_sample.fused_supported(spec)
    assert not fused_sample.fused_supported(spec[:3])
    # forcing per-op still produces the same numbers through sigma_app
    want_sig, want_feat = _fused_eval(cfg, cf, centers, cid, pts, "per-op")
    got_sig, got_feat = _fused_eval(cfg, cf, centers, cid, pts, None)
    np.testing.assert_allclose(np.asarray(got_sig), np.asarray(want_sig),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_feat), np.asarray(want_feat),
                               rtol=1e-5, atol=1e-5)


def test_fused_rank_table_restores():
    """bitmap rank tables are derived state: dropping them (as a restored
    checkpoint would) routes dispatch to per-op until recomputed."""
    import dataclasses
    cfg, cf, centers, cid, pts = _fused_case(0.6, threshold=0.99)
    stripped = {}
    for k, efs in cf.factors.items():
        out = []
        for ef in efs:
            if ef.fmt == "bitmap":
                e = dataclasses.replace(ef)
                e.bitmap = sparse.BitmapEncoded(
                    ef.bitmap.shape, ef.bitmap.words, ef.bitmap.rowptr,
                    ef.bitmap.values, ef.bitmap.nnz, rank=None)
                out.append(e)
            else:
                out.append(ef)
        stripped[k] = tuple(out)
    cf2 = field_lib.CompressedField(stripped, cf.extras, cfg, cf.threshold)
    spec, streams = tensorf.fused_field_inputs(cf2)
    assert spec is None and streams is None
    assert tensorf.hybrid_dispatch(cf2) == "per-op"
    # the fallback still answers correctly
    sig, feat = _fused_eval(cfg, cf2, centers, cid, pts, None)
    np.testing.assert_allclose(np.asarray(sig), np.asarray(cf.sigma(pts)),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- ops API ---
def test_ops_dispatch_ref_on_cpu():
    rng = np.random.RandomState(1)
    w = rng.randn(8, 32).astype(np.float32)
    w[rng.rand(8, 32) < 0.5] = 0
    enc = sparse.encode_bitmap(w)
    x = jnp.asarray(rng.randn(32, 4).astype(np.float32))
    y = ops.bitmap_matmul(enc.words, enc.rowptr, enc.values, x, cols=32)
    np.testing.assert_allclose(np.asarray(y), w @ np.asarray(x), rtol=1e-5)
