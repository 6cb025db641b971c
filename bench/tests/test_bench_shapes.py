"""The required-work functions against the program and against XLA's own
operation count, on the CPU at tiny widths."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.common import ROOT


def arch():
    from bench import harness
    return harness.load_arch("tensorf_vm")


def widths(tiny=True):
    with open(os.path.join(ROOT, "bench", "configs",
                           "tensorf-vm-hybrid.json")) as f:
        conf = json.load(f)
    if tiny:
        conf["field"].update(arch().rehearsal_widths())
    return conf


@pytest.mark.parametrize("look_at", ["occupied_cube", "origin"])
def test_required_samples_equal_processed_samples(look_at):
    """With no early termination and a budget that drops nothing, the
    renderer processes exactly the required samples."""
    from bench import geometry, inputs, shapes, traffic
    from repro.core import occupancy as occ_lib
    from repro.core import pipeline

    conf = widths()
    conf["field"]["term_eps"] = 0.0
    w = conf["field"]
    vm = arch()
    cfg, field = vm.build(conf, w, vm.make_weights(conf, 3))
    occ = inputs.occupancy(conf)
    centers = inputs.cube_centers(conf, occ)
    cubes = occ_lib.extract_cubes(jnp.asarray(occ), cfg)
    mix = {"view": {"h": 16, "w": 16, "focal": 19.2},
           "origin": {"radius": 4.0, "azimuth": [0.0, 6.3],
                      "elevation": [0.2, 0.8]}, "look_at": look_at}
    chunk, n_rays = 8, 256
    render = jax.jit(pipeline.make_ray_renderer(
        cfg, chunk=chunk, pair_budget=chunk * n_rays))
    for pose, _ in zip(traffic.poses(mix, centers, 9), range(3)):
        ro, rd = pose.rays()
        perm = pipeline.order_cubes(cubes, jnp.asarray(pose.origin))
        _, aux = render(field, cubes.centers[perm], cubes.valid[perm],
                        jnp.asarray(ro), jnp.asarray(rd))
        assert int(aux["dropped_pairs"]) == 0
        h = geometry.hits(w, centers, ro, rd, chunk)
        assert shapes.required_samples(w, h) == int(aux["processed_samples"])
        assert shapes.required_samples(w, h) > 0


def xla_ops(fn, *shapes_):
    """Operations per row that XLA's cost analysis counts (flops plus
    transcendentals) for a jitted function of (N, ...) arguments."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes_]
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return (cost["flops"] + cost.get("transcendentals", 0.0)) \
        / shapes_[-1][0]


@pytest.mark.parametrize("tiny", [True, False])
def test_ops_per_sample_against_xla(tiny):
    """The MLP and the basis projection, most of the work, match XLA's
    count exactly; the whole field evaluation within what the reference's
    index arithmetic (clips, floors, repeated weights) adds."""
    vm = arch()
    w = widths(tiny)["field"]
    ops = vm.ops_per_sample(w)
    shp = vm.shapes(w)
    n = 512
    p = {k: v for k, v in shp.items()}
    d_in = shp["mlp_w1"][0]

    def mlp(w1, b1, w2, b2, w3, b3, x):
        h = jax.nn.relu(x @ w1 + b1)
        h = jax.nn.relu(h @ w2 + b2)
        return jax.nn.sigmoid(h @ w3 + b3)
    assert xla_ops(mlp, *(p[k] for k in ("mlp_w1", "mlp_b1", "mlp_w2",
                                         "mlp_b2", "mlp_w3", "mlp_b3")),
                   (n, d_in)) == ops["mlp"]
    assert xla_ops(lambda b, f: f @ b, p["basis"],
                   (n, p["basis"][0])) == ops["basis"]

    def field(*a):
        prm = dict(zip(sorted(shp), a[:-2]))
        return vm.reference_field(prm, w, a[-2], a[-1])
    total = xla_ops(field, *(p[k] for k in sorted(shp)), (n, 3), (n, 3))
    mine = sum(ops.values()) - ops["composite"]
    assert mine <= total <= mine * (1.2 if tiny else 1.03)


def test_bytes_per_view():
    w = widths(tiny=False)["field"]
    mlp = 150 * 128 + 128 * 128 + 128 * 3 + 128 + 128 + 3
    assert arch().bytes_per_view(w, 4096) == 4 * (9 * 4096 + mlp)


def test_scene_arrays_found_once():
    """The scene walk finds each device array once through any path."""
    from bench import harness
    a, b = jnp.ones((4,), jnp.float32), jnp.zeros((2, 3), jnp.int32)

    class Holder:
        def __init__(self):
            self.x = {"a": a, "again": a}
            self.y = (b, [a], np.ones(3))
    found = harness.device_arrays(Holder())
    assert sorted(x.nbytes for x in found) == [16, 24]
