"""The engine's count of processed samples against the benchmark's count
of required ones, on the CPU at tiny widths."""
import jax.numpy as jnp
import numpy as np

from bench.tests.test_bench_shapes import widths


def test_engine_samples_processed_equal_required_samples():
    """With no early termination and a budget that drops nothing, the
    engine's `engine_samples_processed` for a view is exactly the
    required samples of that view."""
    from bench import geometry, harness, inputs, shapes, traffic
    from repro.configs.rtnerf import NeRFConfig
    from repro.core import field as field_lib
    from repro.core import occupancy as occ_lib
    from repro.core import rendering

    conf = widths()
    conf["field"]["term_eps"] = 0.0
    w = conf["field"]
    cfg = NeRFConfig(**w)
    occ = inputs.occupancy(conf)
    centers = inputs.cube_centers(conf, occ)
    cubes = occ_lib.extract_cubes(jnp.asarray(occ), cfg)
    field = field_lib.DenseField(inputs.make_weights(conf, 3), cfg)
    mix = {"view": {"h": 16, "w": 16, "focal": 19.2},
           "origin": {"radius": 4.0, "azimuth": [0.0, 6.3],
                      "elevation": [0.2, 0.8]}, "look_at": "occupied_cube"}
    chunk, n_rays = 8, 256
    from repro.serving.engine import RenderEngine
    engine = RenderEngine(cfg, field, cubes, encode=False, ray_chunk=n_rays,
                          cube_chunk=chunk, pair_budget=chunk * n_rays,
                          adaptive_pair_budget=False)
    counter = engine.metrics.counter("engine_samples_processed")
    for pose, _ in zip(traffic.poses(mix, centers, 11), range(2)):
        cam = harness.camera(pose)
        before = counter.value
        engine.submit(cam).result()
        ro, rd = (np.asarray(a) for a in rendering.camera_rays(cam))
        required = shapes.required_samples(
            w, geometry.hits(w, centers, ro, rd, chunk))
        assert required > 0
        assert counter.value - before == required
    assert engine.stats()["dropped_pairs"] == 0
