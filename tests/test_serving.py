"""Streaming multi-view serving engine: micro-batch packing, request/
response futures, batched-vs-sequential render parity, ordering-cache
reuse, checkpoint-backed field lifecycle, live field hot-swap, and request
deadlines."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import pipeline as rt_pipe
from repro.core import rendering, tensorf
from repro.data import rays as rays_lib
from repro.serving import RenderEngine, plan_microbatches, prepare_field

CFG = NeRFConfig(grid_res=24, occ_res=24, cube_size=4, max_cubes=256,
                 r_sigma=4, r_color=8, app_dim=8, mlp_hidden=16,
                 max_samples_per_ray=64, train_rays=256)


def _field_and_cubes(target=0.9, seed=0):
    params = tensorf.init_field(CFG, jax.random.PRNGKey(seed))
    field = field_lib.DenseField(params, CFG).prune(sparsity=target)
    occ = occ_lib.build_occupancy(field, CFG, sigma_thresh=0.01)
    cubes = occ_lib.extract_cubes(occ, CFG)
    assert cubes.count > 0
    return field, cubes


# -- micro-batching --------------------------------------------------------


def test_plan_microbatches_roundtrip():
    rng = np.random.RandomState(0)
    sizes = [100, 257, 64]
    batches = [(rng.randn(n, 3).astype(np.float32),
                rng.randn(n, 3).astype(np.float32)) for n in sizes]
    plan = plan_microbatches(batches, chunk=128)
    assert plan.total == sum(sizes)
    assert plan.rays_o.shape == (plan.n_chunks, 128, 3)
    assert plan.n_chunks * 128 >= plan.total
    # identity "render": scatter returns each view its own rays
    outs = [plan.rays_o[i] for i in range(plan.n_chunks)]
    views = plan.scatter(outs)
    for (ro, _), got in zip(batches, views):
        np.testing.assert_array_equal(got, ro)


def test_plan_microbatches_empty_rejected():
    with pytest.raises(ValueError):
        plan_microbatches([], chunk=64)


# -- ray renderer vs image-space pipeline ----------------------------------


@pytest.mark.parametrize("encoded", [False, True])
def test_ray_renderer_matches_image_pipeline(encoded):
    """The serving ray renderer must match render_rtnerf on a full view
    (same geometry, compositing, ordering; no tile clipping) for dense and
    encoded fields alike."""
    field, cubes = _field_and_cubes()
    if encoded:
        field = field.encode()
    cam = rays_lib.make_cameras(3, 16, 16)[0]
    img_s, _ = rt_pipe.render_rtnerf(field, CFG, cubes, cam, chunk=8)
    render = rt_pipe.make_ray_renderer(CFG, chunk=8)
    perm = rt_pipe.order_cubes(cubes, cam.origin)
    ro, rd = rendering.camera_rays(cam)
    img_r, aux = render(field, cubes.centers[perm], cubes.valid[perm],
                        ro, rd)
    assert int(aux["dropped_pairs"]) == 0
    psnr = float(rendering.psnr(jnp.clip(img_r, 0, 1),
                                jnp.clip(img_s, 0, 1)))
    assert psnr >= 40.0, psnr


def test_ray_renderer_nondivisible_cube_chunk_keeps_all_cubes():
    """A cube count that doesn't divide cube_chunk must be padded, never
    truncated — with truncation, chunk=8 over 10 cubes would drop 2."""
    field, cubes = _field_and_cubes()
    cam = rays_lib.make_cameras(3, 16, 16)[0]
    ro, rd = rendering.camera_rays(cam)
    c10 = cubes.centers[:10]                  # valid cubes sort first
    v10 = cubes.valid[:10]
    assert bool(np.asarray(v10).all())
    img5, _ = rt_pipe.make_ray_renderer(CFG, chunk=5)(field, c10, v10,
                                                      ro, rd)
    img8, _ = rt_pipe.make_ray_renderer(CFG, chunk=8)(field, c10, v10,
                                                      ro, rd)
    psnr = float(rendering.psnr(jnp.clip(img8, 0, 1), jnp.clip(img5, 0, 1)))
    assert psnr >= 40.0, psnr


def test_ray_renderer_budget_overflow_is_counted():
    field, cubes = _field_and_cubes()
    cam = rays_lib.make_cameras(3, 16, 16)[0]
    render = rt_pipe.make_ray_renderer(CFG, chunk=8, pair_budget=8)
    perm = rt_pipe.order_cubes(cubes, cam.origin)
    ro, rd = rendering.camera_rays(cam)
    img, aux = render(field, cubes.centers[perm], cubes.valid[perm], ro, rd)
    assert int(aux["dropped_pairs"]) > 0     # 8 pairs can't cover the view
    assert np.isfinite(np.asarray(img)).all()


# -- engine ----------------------------------------------------------------


def test_engine_batched_matches_sequential():
    """submit/flush over several views == the sequential per-view loop."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          max_batch_views=8)
    assert engine.field.kind == "compressed"   # encoded at construction
    cams = rays_lib.make_cameras(3, 16, 16)
    futs = [engine.submit(cam) for cam in cams]
    assert not any(f.done() for f in futs)
    results = [f.result() for f in futs]     # result() flushes
    assert all(f.done() for f in futs)
    for cam, r in zip(cams, results):
        img_s, _ = rt_pipe.render_rtnerf(field.encode(), CFG, cubes, cam,
                                         chunk=8)
        psnr = float(rendering.psnr(
            jnp.clip(jnp.asarray(r.img), 0, 1), jnp.clip(img_s, 0, 1)))
        assert psnr >= 40.0, (r.view_id, psnr)
    s = engine.stats()
    assert s["views_served"] == 3
    assert s["dropped_pairs"] == 0
    assert s["latency_p95_s"] >= s["latency_p50_s"] >= 0.0
    assert s["fps"] > 0.0
    assert s["compression_ratio"] >= 3.0     # resident field is encoded
    assert s["occ_accesses_per_view"] == cubes.count


def test_engine_encode_false_serves_dense():
    """encode=False is a real dense/compressed toggle: a pre-encoded field
    is decoded, so the dense baseline actually measures the dense path."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field.encode(), cubes, encode=False,
                          ray_chunk=16 * 16)
    assert engine.field.kind == "dense"
    s = engine.stats()
    assert s["field_kind"] == "dense"
    assert s["compression_ratio"] == 1.0


def test_engine_ordering_cache_reused_across_requests():
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          max_batch_views=16)
    # 4 views on a circle: octants repeat -> schedules are reused
    cams = rays_lib.make_cameras(4, 16, 16)
    engine.render_views(cams)
    oc = engine.stats()["ordering_cache"]
    assert oc["hits"] + oc["misses"] == 4
    assert oc["entries"] == oc["misses"] <= 4
    # a second pass over the same cameras is all hits
    engine.render_views(cams)
    oc2 = engine.stats()["ordering_cache"]
    assert oc2["misses"] == oc["misses"]
    assert oc2["hits"] == oc["hits"] + 4
    # occupancy rebuild invalidates
    engine.update_cubes(cubes)
    assert engine.stats()["ordering_cache"]["entries"] == 0


def test_engine_auto_flush_at_max_batch():
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          max_batch_views=2)
    f1 = engine.submit(rays_lib.make_cameras(3, 16, 16)[0])
    assert not f1.done()
    f2 = engine.submit(rays_lib.make_cameras(3, 16, 16)[1])
    assert f1.done() and f2.done()           # queue hit max_batch_views


def test_engine_psnr_against_gt_is_reported():
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16)
    cam = rays_lib.make_cameras(3, 16, 16)[0]
    gt = np.zeros((16 * 16, 3), np.float32)
    r = engine.submit(cam, gt).result()
    assert r.psnr is not None and np.isfinite(r.psnr)
    assert r.latency_s > 0.0
    assert r.stats["factor_bytes"] > 0


def test_engine_mixed_resolutions_share_one_step():
    """Views at different resolutions micro-batch into the same chunks."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=256,
                          max_batch_views=8)
    cams = [rays_lib.make_cameras(3, 16, 16)[0],
            rays_lib.make_cameras(3, 24, 24)[1]]
    res = engine.render_views(cams)
    assert res[0].img.shape == (16 * 16, 3)
    assert res[1].img.shape == (24 * 24, 3)
    for r in res:
        assert np.isfinite(r.img).all()
    # padding rays originate outside the scene: no pad may register hits
    # and eat pair-budget slots from real rays
    assert engine.stats()["dropped_pairs"] == 0


# -- request deadlines -----------------------------------------------------


def test_engine_deadline_expired_requests_time_out():
    """A request past its deadline resolves with a timeout result instead
    of being rendered late; live requests in the same flush still render."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          max_batch_views=16)
    cams = rays_lib.make_cameras(3, 16, 16)
    stale = engine.submit(cams[0], deadline_s=-1.0)    # already expired
    live = engine.submit(cams[1], deadline_s=600.0)
    engine.flush()
    r_stale, r_live = stale.result(), live.result()
    assert r_stale.timed_out and r_stale.img is None
    assert r_stale.psnr is None
    assert not r_live.timed_out
    assert np.isfinite(r_live.img).all()
    s = engine.stats()
    assert s["timeouts"] == 1
    assert s["views_served"] == 1            # the timeout never rendered


def test_engine_no_deadline_never_times_out():
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16)
    r = engine.submit(rays_lib.make_cameras(3, 16, 16)[0]).result()
    assert not r.timed_out
    assert engine.stats()["timeouts"] == 0


def test_engine_deadline_fires_during_stalled_flush(stall_render):
    """Deadlines must hold even when the flush thread itself is slow: with
    the render artificially stalled (conftest `stall_render` fault
    injector), a short-deadline request queued behind the stalled flush
    still resolves as a timeout at the next cycle — it is never rendered
    late and never hangs."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          auto_flush_interval=0.05)
    try:
        cams = rays_lib.make_cameras(3, 16, 16)
        engine.submit(cams[0]).result(timeout=120.0)   # warm the jit path
        handle = stall_render(engine, delay_s=0.8)
        slow = engine.submit(cams[1])                  # no deadline
        assert handle.entered.wait(30.0)               # flush is stalling
        stale = engine.submit(cams[2], deadline_s=0.05)
        r_stale = stale.result(timeout=60.0)
        r_slow = slow.result(timeout=60.0)
        assert r_stale.timed_out and r_stale.img is None
        assert not r_slow.timed_out
        assert np.isfinite(r_slow.img).all()
        assert engine.stats()["timeouts"] == 1
    finally:
        engine.close()


# -- live field hot-swap ---------------------------------------------------


def test_engine_swap_field_changes_served_field():
    """After swap_field, new requests render from the published field (and
    match a direct render of it); the occupancy cube set is rebuilt."""
    field, cubes = _field_and_cubes(seed=0)
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16)
    cam = rays_lib.make_cameras(3, 16, 16)[0]
    img_before = engine.submit(cam).result().img

    field2, cubes2 = _field_and_cubes(seed=7)
    engine.swap_field(field2)                 # cubes rebuilt from field2
    img_after = engine.submit(cam).result().img
    ref, _ = rt_pipe.render_rtnerf(field2.encode(), CFG, engine.cubes, cam,
                                   chunk=8)
    psnr = float(rendering.psnr(jnp.clip(jnp.asarray(img_after), 0, 1),
                                jnp.clip(ref, 0, 1)))
    assert psnr >= 40.0, psnr
    # the two fields are different scenes-worth of params: images differ
    assert float(np.abs(img_after - img_before).mean()) > 1e-4
    s = engine.stats()
    assert s["field_swaps"] == 1
    assert s["ordering_cache"]["entries"] <= 1   # invalidated on swap


def test_engine_swap_field_under_concurrent_submits():
    """Acceptance: swap_field while producer threads submit — every future
    resolves (rendered by old or new field, or after the swap), none are
    dropped, and the engine stays consistent."""
    field, cubes = _field_and_cubes(seed=0)
    field2, _ = _field_and_cubes(seed=7)
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16,
                          max_batch_views=3)
    cams = rays_lib.make_cameras(6, 16, 16)
    futs, errs = [], []

    def producer(tid):
        try:
            for i in range(4):
                futs.append(engine.submit(cams[(tid + i) % len(cams)]))
        except BaseException as e:            # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(3)]
    for t in threads:
        t.start()
    engine.swap_field(field2)                 # races with the submits
    for t in threads:
        t.join()
    engine.flush()
    assert not errs
    assert len(futs) == 12
    for f in futs:
        r = f.result()
        assert not r.timed_out
        assert np.isfinite(r.img).all()
    s = engine.stats()
    assert s["views_served"] == 12
    assert s["field_swaps"] == 1


# -- checkpoint-backed field lifecycle -------------------------------------


def test_prepare_field_trains_once_then_restores(tmp_path):
    from repro.ckpt import checkpoint as ckpt_lib

    ckpt = str(tmp_path / "ckpt")
    f1 = prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=3,
                       n_views=2, image_hw=16, verbose=False)
    step = ckpt_lib.latest_step(ckpt)
    assert step == 3                          # trained + checkpointed
    f2 = prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=3,
                       n_views=2, image_hw=16, verbose=False)
    assert f2.kind == f1.kind
    p1, p2 = f1.decode().params, f2.decode().params
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(p2[k]))
    # the restore path really is a restore: the checkpoint step is unchanged
    assert ckpt_lib.latest_step(ckpt) == step


def test_prepare_field_restores_encoded_representation(tmp_path):
    """Compressed-native training checkpoints the ENCODED field; a restore
    hands back the same representation without decompressing."""
    ckpt = str(tmp_path / "ckpt")
    f1 = prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=3,
                       n_views=2, image_hw=16, verbose=False)
    assert f1.kind == "compressed"            # train_nerf default
    f2 = prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=3,
                       n_views=2, image_hw=16, verbose=False)
    assert f2.kind == "compressed"
    assert f2.sparsity_report() == f1.sparsity_report()
    assert f2.factor_bytes() == f1.factor_bytes()


def test_prepare_field_restores_legacy_params_checkpoint(tmp_path):
    """Checkpoints from before the FieldBackend refactor (raw params dict,
    no field_spec) must still restore — as a dense field — instead of
    crashing the serve path."""
    import json

    from repro.ckpt import checkpoint as ckpt_lib

    ckpt = str(tmp_path / "ckpt")
    params = tensorf.init_field(CFG, jax.random.PRNGKey(3))
    ckpt_lib.save_checkpoint(ckpt, 5, params)          # legacy format
    with open(str(tmp_path / "ckpt" / "field_meta.json"), "w") as f:
        json.dump({"scene": "lego", "steps": 5, "seed": 0}, f)
    restored = prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=5,
                             n_views=2, image_hw=16, verbose=False)
    assert restored.kind == "dense"
    for k in params:
        np.testing.assert_array_equal(np.asarray(restored.params[k]),
                                      np.asarray(params[k]))


def test_stream_sharding_multidevice():
    """8 virtual devices: encoded streams replicate, ray chunks shard over
    the data axis (a non-divisible chunk is refused, never replicated),
    and the engine renders correctly on the mesh."""
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.rtnerf import NeRFConfig
    from repro.core import distributed, field as field_lib
    from repro.core import occupancy as occ_lib, tensorf
    from repro.data import rays as rays_lib
    from repro.models.sharding import make_rules
    from repro.serving import RenderEngine

    cfg = NeRFConfig(grid_res=16, occ_res=16, cube_size=4, max_cubes=64,
                     r_sigma=2, r_color=4, app_dim=4, mlp_hidden=8,
                     max_samples_per_ray=32, train_rays=64)
    field = field_lib.DenseField(
        tensorf.init_field(cfg, jax.random.PRNGKey(0)), cfg).prune(
        sparsity=0.9)
    occ = occ_lib.build_occupancy(field, cfg, sigma_thresh=0.01)
    cubes = occ_lib.extract_cubes(occ, cfg)

    mesh = jax.make_mesh((8, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = make_rules(mesh)
    cf = distributed.place_field(field.encode(), rules)
    for leaf in jax.tree.leaves(cf):
        assert leaf.sharding.is_fully_replicated
    ro, rd = distributed.shard_rays(rules, jnp.zeros((256, 3)),
                                    jnp.zeros((256, 3)))
    assert not ro.sharding.is_fully_replicated        # 256 % 8 == 0: sharded
    try:                                  # 100 % 8 != 0: no silent
        distributed.shard_rays(rules, jnp.zeros((100, 3)),  # replication
                               jnp.zeros((100, 3)))
        raise AssertionError("non-divisible chunk was placed")
    except ValueError:
        pass
    try:
        RenderEngine(cfg, cf, cubes, ray_chunk=100, mesh=mesh)
        raise AssertionError("engine accepted a non-divisible ray_chunk")
    except ValueError:
        pass

    eng = RenderEngine(cfg, cf, cubes, ray_chunk=256, mesh=mesh)
    r = eng.submit(rays_lib.make_cameras(3, 16, 16)[0]).result()
    assert np.isfinite(r.img).all()
    assert eng.stats()["n_devices"] == 8
    print("serving sharding ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "serving sharding ok" in r.stdout


def test_prepare_field_rejects_cfg_mismatch(tmp_path):
    """A checkpoint trained under another NeRFConfig must fail loudly on
    restore (shape comparison through the encoded spec), not serve a
    distorted field."""
    ckpt = str(tmp_path / "ckpt")
    prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=2, n_views=2,
                  image_hw=16, verbose=False)
    other = NeRFConfig(grid_res=16, occ_res=16, cube_size=4, max_cubes=64,
                       r_sigma=2, r_color=4, app_dim=4, mlp_hidden=8,
                       max_samples_per_ray=32, train_rays=64)
    with pytest.raises(ValueError, match="different"):
        prepare_field(other, "lego", ckpt_dir=ckpt, train_steps=2,
                      n_views=2, image_hw=16, verbose=False)


def test_prepare_field_rejects_scene_mismatch(tmp_path):
    """One ckpt dir holds one scene; restoring it for another scene must
    fail loudly instead of serving the wrong field."""
    ckpt = str(tmp_path / "ckpt")
    prepare_field(CFG, "lego", ckpt_dir=ckpt, train_steps=2, n_views=2,
                  image_hw=16, verbose=False)
    with pytest.raises(ValueError, match="scene"):
        prepare_field(CFG, "chair", ckpt_dir=ckpt, train_steps=2,
                      n_views=2, image_hw=16, verbose=False)


def test_engine_flush_failure_requeues(monkeypatch):
    """A render error must not strand queued futures: requests go back on
    the queue and the next flush resolves them."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=16 * 16)
    fut = engine.submit(rays_lib.make_cameras(3, 16, 16)[0])
    good_render = engine._render
    calls = {"n": 0}

    def flaky(*a):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return good_render(*a)

    monkeypatch.setattr(engine, "_render", flaky)
    with pytest.raises(RuntimeError, match="transient"):
        engine.flush()
    assert not fut.done()
    assert engine.stats()["views_served"] == 0   # nothing resolved, none
    r = fut.result()                             # counted; retry via flush
    assert np.isfinite(r.img).all()
    assert engine.stats()["views_served"] == 1
    assert len(engine._latencies) == 1           # latencies match the count


def test_engine_from_scene_with_ckpt(tmp_path):
    engine = RenderEngine.from_scene(
        CFG, "lego", ckpt_dir=str(tmp_path / "ckpt"), train_steps=3,
        n_views=2, image_hw=16, prune_sparsity=0.9, verbose=False,
        ray_chunk=16 * 16)
    assert engine.field.kind == "compressed"
    r = engine.submit(rays_lib.make_cameras(3, 16, 16)[0]).result()
    assert np.isfinite(r.img).all()
