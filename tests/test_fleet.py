"""Fleet tier: consistent-hash ring properties (fast, in-process) and the
multi-process router/worker integration suite (`@pytest.mark.fleet` —
spawns real worker processes; run with --fleet / REPRO_FLEET=1 or by
invoking this file directly, as the CI fleet-smoke job does).

The integration tests cover the failure contract promised in
docs/architecture.md: affinity stable under registry churn, ~1/K key
movement on membership change, bit-identical frames from replicas, and a
SIGKILLed worker leaving no future unresolved.
"""
import os
import time

import jax
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import tensorf
from repro.data import rays as rays_lib
from repro.serving import FleetError, FleetRouter, HashRing, export_scene

CFG = NeRFConfig(grid_res=24, occ_res=24, cube_size=4, max_cubes=256,
                 r_sigma=4, r_color=8, app_dim=8, mlp_hidden=16,
                 max_samples_per_ray=64, train_rays=256)

SCENES = ["alpha", "beta", "gamma"]


# -- hash ring (fast, no processes) ----------------------------------------


def _keys(n=400):
    return [f"scene-{i}" for i in range(n)]


def test_ring_deterministic_and_total():
    ring = HashRing(["w0", "w1", "w2"])
    again = HashRing(["w2", "w0", "w1"])      # insertion order is irrelevant
    for k in _keys():
        assert ring.owner(k) == again.owner(k)
        assert ring.owner(k) in ("w0", "w1", "w2")


def test_ring_owners_distinct_and_capped():
    ring = HashRing(["w0", "w1", "w2"])
    for k in _keys(50):
        owners = ring.owners(k, 2)
        assert len(owners) == 2 and len(set(owners)) == 2
        assert ring.owners(k, 10) and len(ring.owners(k, 10)) == 3
        assert owners[0] == ring.owner(k)


def test_ring_version_tracks_membership():
    ring = HashRing()
    assert ring.version == 0
    ring.add("w0")
    ring.add("w0")                            # idempotent: no version bump
    assert ring.version == 1
    ring.add("w1")
    ring.remove("w0")
    ring.remove("w0")
    assert ring.version == 3
    assert ring.nodes == ["w1"]


@given(st.integers(2, 6))
def test_ring_leave_moves_only_dead_workers_keys(k):
    """Removing a worker must not remap any key that worker didn't own."""
    nodes = [f"w{i}" for i in range(k)]
    ring = HashRing(nodes)
    before = {key: ring.owner(key) for key in _keys()}
    dead = nodes[0]
    ring.remove(dead)
    for key, owner in before.items():
        if owner != dead:
            assert ring.owner(key) == owner
        else:
            assert ring.owner(key) != dead


@given(st.integers(1, 6))
def test_ring_join_moves_about_one_over_k(k):
    """A joining worker takes ~1/(K+1) of the keyspace — and every moved
    key moves TO it (the consistent-hashing contract that keeps worker
    churn from invalidating every worker's resident set)."""
    nodes = [f"w{i}" for i in range(k)]
    ring = HashRing(nodes)
    keys = _keys(600)
    before = {key: ring.owner(key) for key in keys}
    ring.add("joiner")
    moved = [key for key in keys if ring.owner(key) != before[key]]
    for key in moved:
        assert ring.owner(key) == "joiner"
    # expectation is 1/(k+1); allow generous slack for vnode variance
    assert len(moved) / len(keys) <= 2.5 / (k + 1)


def test_router_refuses_tpu_backend(monkeypatch):
    """Fleet workers are processes and a TPU chip serves one process at a
    time: on a tpu backend the router refuses before spawning anyone."""
    import multiprocessing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spawned = []
    monkeypatch.setattr(multiprocessing, "get_context",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(RuntimeError, match="one process at a time"):
        FleetRouter(CFG, {}, n_workers=2)
    assert not spawned


# -- multi-process integration ---------------------------------------------


def _export_scenes(root):
    paths = {}
    for i, name in enumerate(SCENES):
        params = tensorf.init_field(CFG, jax.random.PRNGKey(i))
        field = field_lib.DenseField(params, CFG).prune(sparsity=0.9)
        occ = occ_lib.build_occupancy(field, CFG, sigma_thresh=0.01)
        cubes = occ_lib.extract_cubes(occ, CFG)
        paths[name] = export_scene(str(root / name), field.encode(), cubes,
                                   scene=name)
    return paths


@pytest.fixture(scope="module")
def scene_paths(tmp_path_factory):
    return _export_scenes(tmp_path_factory.mktemp("fleet_scenes"))


@pytest.fixture(scope="module")
def fleet(scene_paths):
    """Shared 2-worker fleet for the non-destructive tests (spawn + jit
    warm-up is the expensive part; the kill test builds its own)."""
    router = FleetRouter(CFG, scene_paths, n_workers=2)
    yield router
    router.close()


CAM = rays_lib.make_cameras(1, 16, 16)[0]


def _render(router, scene, **kw):
    return router.submit(CAM, scene=scene, **kw).result(timeout=180.0)


@pytest.mark.fleet
def test_affinity_stable_under_churn(fleet):
    """Register/evict/revive churn must not move a scene's owner, and the
    revived scene must serve the identical frame (bit-for-bit spill
    round-trip, now across a process boundary)."""
    scene = SCENES[0]
    owner0 = fleet.owner_of(scene)
    version0 = fleet.ring.version
    r0 = _render(fleet, scene)
    assert not r0.timed_out and r0.img is not None

    fleet.evict(scene)                       # registry churn: spill ...
    assert fleet.owner_of(scene) == owner0
    r1 = _render(fleet, scene)               # ... auto-revive on touch
    np.testing.assert_array_equal(r0.img, r1.img)

    fleet.evict(scene)
    fleet.prefetch(scene)                    # ... async revive
    r2 = _render(fleet, scene)
    np.testing.assert_array_equal(r0.img, r2.img)

    assert fleet.owner_of(scene) == owner0
    assert fleet.ring.version == version0    # churn != membership change
    stats = fleet.stats()
    assert stats["prefetches_total"] == 1
    assert stats["workers_alive"] == 2


@pytest.mark.fleet
def test_replicated_scene_bit_identical_across_replicas(fleet):
    """A hot scene behind one key, resident on both workers: frames must
    be bit-identical regardless of which replica served them."""
    scene = SCENES[1]
    fleet.set_replicas(scene, 2)
    replicas = fleet.replica_workers(scene)
    assert len(replicas) == 2
    imgs = []
    for worker in replicas:
        r = _render(fleet, scene, prefer_worker=worker)
        assert r.worker == worker and not r.timed_out
        imgs.append(r.img)
    np.testing.assert_array_equal(imgs[0], imgs[1])
    snap = fleet.registry.snapshot()["gauges"]
    assert snap[f"fleet_replicas{{scene={scene}}}"]["value"] == 2


@pytest.mark.fleet
def test_slow_worker_deadline_fires(fleet, fleet_faults):
    """Injected pre-flush stall on the owner: a request with a shorter
    deadline must come back as a timed-out result (engine deadline
    semantics hold across the wire), then the worker recovers."""
    scene = SCENES[2]
    owner = fleet.owner_of(scene)
    _render(fleet, scene)                    # warm (register + jit) first
    fleet_faults.stall(fleet, owner, 1.0)
    try:
        r = fleet.submit(CAM, scene=scene, deadline_s=0.05,
                         prefer_worker=owner).result(timeout=60.0)
        assert r.timed_out and r.img is None
    finally:
        fleet_faults.stall(fleet, owner, 0.0)
    r2 = _render(fleet, scene, prefer_worker=owner)
    assert not r2.timed_out and r2.img is not None


@pytest.mark.fleet
def test_router_survives_sigkilled_worker(scene_paths, fleet_faults):
    """SIGKILL a worker with requests in flight: every future resolves
    (replayed result on the survivor, or timed-out for already-expired
    deadlines — never hung), the ring re-hashes, and the fleet keeps
    serving."""
    router = FleetRouter(CFG, scene_paths, n_workers=2)
    try:
        scene = SCENES[0]
        victim = router.owner_of(scene)
        survivor = [w for w in router.alive_workers() if w != victim][0]
        baseline = _render(router, scene, prefer_worker=survivor)
        version0 = router.ring.version

        # Stall the victim so its queue holds real in-flight requests,
        # then kill it mid-stall.
        _render(router, scene, prefer_worker=victim)       # warm victim
        fleet_faults.stall(router, victim, 5.0)
        live = [router.submit(CAM, scene=scene, prefer_worker=victim)
                for _ in range(3)]
        expired = router.submit(CAM, scene=scene, deadline_s=0.01,
                                prefer_worker=victim)
        time.sleep(0.5)                       # let the sends land
        fleet_faults.kill(router, victim)

        results = [f.result(timeout=180.0) for f in live]
        for r in results:
            assert not r.timed_out and r.img is not None
            assert r.replayed and r.worker == survivor
            np.testing.assert_array_equal(r.img, baseline.img)
        rexp = expired.result(timeout=60.0)
        assert rexp.timed_out and rexp.img is None

        assert router.alive_workers() == [survivor]
        assert router.ring.version == version0 + 1
        stats = router.stats()
        assert stats["worker_deaths"] == 1
        assert stats["replays_total"] >= 3
        # dead worker refuses new preferred traffic; affinity re-hashed
        with pytest.raises(FleetError):
            router.submit(CAM, scene=scene, prefer_worker=victim)
        assert router.owner_of(scene) == survivor
        r_after = _render(router, scene)
        assert not r_after.timed_out
        np.testing.assert_array_equal(r_after.img, baseline.img)
    finally:
        router.close()


@pytest.mark.fleet
def test_fleet_metrics_schema(fleet):
    """The fleet_* families promised to scripts/check_metrics_schema.py
    exist on the router registry after traffic."""
    _render(fleet, SCENES[0])
    fleet.poll_stats()                       # refreshes per-worker gauges
    snap = fleet.registry.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    for fam in ("fleet_requests_total", "fleet_results_total",
                "fleet_registrations_total"):
        assert any(k.startswith(fam + "{") for k in counters), fam
    for fam in ("fleet_routing_version", "fleet_workers_alive"):
        assert fam in gauges, fam
    for fam in ("fleet_outstanding", "fleet_worker_fps",
                "fleet_worker_queue_depth", "fleet_worker_evictions"):
        assert any(k.startswith(fam + "{") for k in gauges), fam
    assert "fleet_latency_s" in snap["histograms"]
