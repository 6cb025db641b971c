"""A run with the timed path broken underneath must come out not correct.

Each test plants one fault in the program (the harness's chip check is
skipped: rehearsal at tiny widths on the CPU) and drives a whole traced
run of one view."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.common import rehearse


@pytest.fixture(autouse=True)
def fresh_jit():
    jax.clear_caches()             # a planted fault must not hit a program
    yield                          # compiled before it was planted
    jax.clear_caches()


def wrap_renderer(monkeypatch, post):
    """Make every render step pass its (rgb, aux) through `post`."""
    from repro.core import pipeline
    orig = pipeline.make_ray_renderer

    def make(cfg, **kw):
        render = orig(cfg, **kw)

        def broken(field, centers, valid, rays_o, rays_d):
            return post(*render(field, centers, valid, rays_o, rays_d),
                        rays_o)
        return broken
    monkeypatch.setattr(pipeline, "make_ray_renderer", make)


def expect_incorrect(cell="hybrid-fovea"):
    rc, res = rehearse(cell, seed=31, trace=True)
    assert rc == 0
    assert res["correct"] is False, res["check"]


def test_step_returning_its_state_unchanged(monkeypatch):
    # the scan's initial state: no colour, transmittance 1 (white)
    wrap_renderer(monkeypatch, lambda rgb, aux, ro: (jnp.ones_like(rgb), aux))
    expect_incorrect()


def test_half_the_batch_left_out(monkeypatch):
    def half(rgb, aux, ro):
        n = rgb.shape[0] // 2
        return rgb.at[n:].set(1.0), aux
    wrap_renderer(monkeypatch, half)
    expect_incorrect()


def test_answer_altered_where_produced(monkeypatch):
    wrap_renderer(monkeypatch,
                  lambda rgb, aux, ro: (rgb.at[100, 1].add(0.25), aux))
    expect_incorrect()


def test_dropped_pairs(monkeypatch):
    from bench import harness
    from repro.serving.engine import RenderEngine

    def small_budget(cfg, field, cubes, conf, scene, mesh):
        return RenderEngine(cfg, field, cubes, scene_name=scene,
                            encode=bool(conf["encode"]), mesh=mesh,
                            pair_budget=128, adaptive_pair_budget=False)
    monkeypatch.setattr(harness, "make_engine", small_budget)
    expect_incorrect()


def test_ordering_reversed(monkeypatch):
    from repro.core import pipeline
    orig = pipeline.order_cubes

    def back_to_front(cubes, origin, mode="octant"):
        perm = orig(cubes, origin, mode)
        n = int(cubes.count)
        return jnp.concatenate([perm[:n][::-1], perm[n:]])
    monkeypatch.setattr(pipeline, "order_cubes", back_to_front)
    expect_incorrect()
