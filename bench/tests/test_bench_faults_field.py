"""Faults planted in field evaluation and geometry: each hybrid decoder,
the dense gather, the colour MLP and the ray-cube intersection."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.test_bench_faults import expect_incorrect


@pytest.fixture(autouse=True)
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def break_decoder(monkeypatch, fmt):
    from repro.kernels import fused_sample
    orig = fused_sample._decode_cols

    def decode(fs, arrs, cols, *, searchsorted):
        out = orig(fs, arrs, cols, searchsorted=searchsorted)
        return jnp.zeros_like(out) if fs[0] == fmt else out
    monkeypatch.setattr(fused_sample, "_decode_cols", decode)


def test_coo_decoder(monkeypatch):
    break_decoder(monkeypatch, "coo")              # the density factors
    expect_incorrect()


def test_bitmap_decoder(monkeypatch):
    break_decoder(monkeypatch, "bitmap")           # the appearance factors
    expect_incorrect()


def test_dense_gather(monkeypatch):
    from repro.core import tensorf
    orig = tensorf._interp_plane
    monkeypatch.setattr(tensorf, "_interp_plane",
                        lambda plane, u, v: orig(plane, v, u))
    expect_incorrect("dense-fovea")


def test_colour_mlp(monkeypatch):
    from repro.core import tensorf
    orig = tensorf.eval_color
    monkeypatch.setattr(
        tensorf, "eval_color",
        lambda params, cfg, feats, dirs: orig(params, cfg, feats, -dirs))
    expect_incorrect()


def test_intersection(monkeypatch):
    from repro.configs.rtnerf import NeRFConfig
    orig = NeRFConfig.cube_world
    monkeypatch.setattr(NeRFConfig, "cube_world",
                        lambda self: 0.75 * orig(self))
    expect_incorrect()
