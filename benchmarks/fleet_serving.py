"""Fleet serving benchmark: aggregate FPS + p95 under a zipfian
scene-popularity workload, single worker vs sharded fleet.

    PYTHONPATH=src python benchmarks/fleet_serving.py --tiny --check

What it measures
----------------
S scenes, each exported once (`serving.fleet.export_scene`), served
through `FleetRouter` twice with the SAME per-worker memory budget and
the SAME request sequence: once with 1 worker, once with `--workers N`
(default 2). The budget holds ~S/N scenes, so the single worker LRU-
thrashes — every touch of a non-resident scene pays a spill + revive
cycle — while the sharded fleet keeps each worker's shard fully
resident. That residency locality is the fleet tier's core claim (and
RT-NeRF's: hybrid encodings pay off when hot scenes stay near their
requests), and it is what the `--check` gate certifies:

  * aggregate FPS at N workers >= 1.5x the single worker,
  * zero dropped non-deadline requests in either run.

On multi-core CI runners the fleet additionally wins from real process
parallelism; on a single-core box the gate is carried by churn avoidance
alone, which is why the workload is closed-loop (one request in flight,
as an interactive AR/VR client would be) — back-pressure batching would
let the single worker amortise its churn across a flush group and hide
the locality signal this benchmark exists to expose.

Scenes are random-init pruned fields (`--no-train` is implicit): the
workload exercises the serving path — routing, residency, eviction,
revival, wire framing — where radiance quality is irrelevant; training
would add minutes of setup to measure the same path. Scene names are
chosen so the consistent-hash ring splits them evenly across the fleet
(a 3/1 split would leave one worker over budget and the comparison
meaningless); popularity ranks alternate workers so each holds hot and
cold scenes.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

TINY = dict(grid_res=16, occ_res=16, cube_size=8, max_cubes=16,
            r_sigma=2, r_color=4, app_dim=4, mlp_hidden=8,
            max_samples_per_ray=16, train_rays=256)
FULL = dict(grid_res=24, occ_res=24, cube_size=8, max_cubes=64,
            r_sigma=4, r_color=8, app_dim=8, mlp_hidden=16,
            max_samples_per_ray=32, train_rays=256)


def pick_balanced_scenes(n_scenes, n_workers):
    """Scene names the ring splits evenly across `n_workers`, popularity
    ranks alternating workers (each worker gets hot AND cold scenes)."""
    from repro.serving import HashRing

    ring = HashRing([f"w{i}" for i in range(n_workers)])
    per_worker = n_scenes // n_workers
    buckets = {f"w{i}": [] for i in range(n_workers)}
    i = 0
    while any(len(b) < per_worker for b in buckets.values()):
        name = f"scene_{i:03d}"
        owner = ring.owner(name)
        if len(buckets[owner]) < per_worker:
            buckets[owner].append(name)
        i += 1
        if i > 10_000:          # pragma: no cover - sha1 would have to be
            raise RuntimeError("could not balance scene names")  # broken
    # rank r -> worker r % n_workers, so popularity alternates owners
    return [buckets[f"w{r % n_workers}"][r // n_workers]
            for r in range(n_scenes)]


def export_scenes(cfg, names, root):
    import jax

    from repro.core import field as field_lib
    from repro.core import occupancy as occ_lib
    from repro.core import tensorf
    from repro.serving import export_scene

    paths = {}
    for i, name in enumerate(names):
        params = tensorf.init_field(cfg, jax.random.PRNGKey(i))
        field = field_lib.DenseField(params, cfg).prune(sparsity=0.9)
        occ = occ_lib.build_occupancy(field, cfg,
                                      sigma_thresh=0.01)
        cubes = occ_lib.extract_cubes(occ, cfg)
        paths[name] = export_scene(os.path.join(root, name), field.encode(),
                                   cubes, scene=name)
    one = field_lib.as_backend(
        field_lib.DenseField(tensorf.init_field(cfg, jax.random.PRNGKey(0)),
                             cfg).prune(sparsity=0.9), cfg
    ).encode().factor_bytes()
    return paths, one


def zipf_pmf(n, s):
    p = 1.0 / np.arange(1, n + 1) ** s
    return p / p.sum()


def build_workload(names, n_requests, n_streams, zipf_s, seed):
    """Round-robin interleave of `n_streams` closed-loop users, each
    drawing its scene iid from the zipf popularity law. The interleave is
    what defeats single-worker LRU: consecutive requests rarely repeat a
    scene, so a budget of S/N scenes misses on most touches."""
    rng = np.random.default_rng(seed)
    pmf = zipf_pmf(len(names), zipf_s)
    per = int(np.ceil(n_requests / n_streams))
    draws = [rng.choice(len(names), size=per, p=pmf)
             for _ in range(n_streams)]
    seq = []
    for t in range(per):
        for u in range(n_streams):
            seq.append((u, names[draws[u][t]]))
    return seq[:n_requests]


def run_fleet(cfg, paths, names, workload, cams, *, n_workers, budget,
              res, warmup_rounds=4):
    from repro.serving import FleetRouter

    router = FleetRouter(cfg, paths, n_workers=n_workers,
                         engine_kwargs=dict(max_resident_bytes=budget,
                                            ray_chunk=res * res))
    try:
        # warm every (scene, viewpoint): registers scenes on their owners,
        # compiles each worker's jit step, settles the adaptive pair
        # budget — the timed loop then measures steady-state serving.
        for _ in range(warmup_rounds):
            for name in names:
                for cam in cams:
                    router.submit(cam, scene=name).result(timeout=300.0)

        # best-of-2 timed passes (the steady_state idiom): one-core boxes
        # timeshare noisily, and the gate compares two measured numbers.
        drops, wall, latencies = 0, None, None
        for _ in range(2):
            lat = []
            t0 = time.perf_counter()
            for user, name in workload:
                r = router.submit(cams[user % len(cams)],
                                  scene=name).result(timeout=300.0)
                if r.timed_out or r.img is None:
                    drops += 1
                lat.append(r.latency_s)
            w = time.perf_counter() - t0
            if wall is None or w < wall:
                wall, latencies = w, lat

        stats = router.stats()
        lat = np.asarray(latencies)
        return {
            "workers": n_workers,
            "aggregate_fps": len(workload) / wall,
            "wall_s": wall,
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95)),
            "dropped": drops,
            "requests": len(workload),
            "routing_version": stats["routing_version"],
            "replays": stats["replays_total"],
            "worker_stats": {
                w: {k: s[k] for k in ("views_served", "fps", "evictions",
                                      "revivals", "resident_scenes",
                                      "queue_depth")}
                for w, s in stats["workers"].items()},
        }, router
    except BaseException:
        router.close()
        raise


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes (CI gate)")
    ap.add_argument("--workers", type=int, default=2,
                    help="fleet size to compare against 1 worker")
    ap.add_argument("--scenes", type=int, default=None,
                    help="number of scenes (default 4 tiny / 6 full)")
    ap.add_argument("--requests", type=int, default=None,
                    help="timed requests (default 120 tiny / 300 full)")
    ap.add_argument("--streams", type=int, default=6,
                    help="interleaved closed-loop user streams")
    ap.add_argument("--zipf", type=float, default=0.9,
                    help="zipf popularity exponent")
    ap.add_argument("--res", type=int, default=None,
                    help="view resolution (default 8 tiny / 16 full)")
    ap.add_argument("--budget-scenes", type=float, default=None,
                    help="per-worker budget in units of one scene's "
                         "factor bytes (default: scenes/workers + 0.5)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_fleet.json"))
    ap.add_argument("--metrics-dump", default=None,
                    help="write the fleet run's obs registry snapshot")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the fleet gate holds")
    args = ap.parse_args()

    import jax

    from repro.configs.rtnerf import NeRFConfig
    from repro.data import rays as rays_lib
    from repro.obs import snapshot_json

    # fleet workers are processes, which cannot share a TPU chip, so this
    # benchmark runs on the host CPU: its numbers are never chip numbers
    dev = jax.devices()[0]
    platform = f"{dev.platform}/{dev.device_kind}"
    print(f"[fleet] platform {platform}")

    shape = TINY if args.tiny else FULL
    cfg = NeRFConfig(**shape)
    n_scenes = args.scenes or (4 if args.tiny else 6)
    n_requests = args.requests or (160 if args.tiny else 300)
    res = args.res or (8 if args.tiny else 16)
    budget_scenes = (args.budget_scenes if args.budget_scenes is not None
                     else n_scenes / args.workers + 0.5)

    names = pick_balanced_scenes(n_scenes, args.workers)
    root = tempfile.mkdtemp(prefix="fleet_bench_")
    try:
        t0 = time.perf_counter()
        paths, one_scene_bytes = export_scenes(cfg, names, root)
        export_s = time.perf_counter() - t0
        budget = int(budget_scenes * one_scene_bytes)
        workload = build_workload(names, n_requests, args.streams,
                                  args.zipf, args.seed)
        cams = rays_lib.make_cameras(3, res, res)

        runs = {}
        dump_router = None
        for w in (1, args.workers):
            t0 = time.perf_counter()
            result, router = run_fleet(cfg, paths, names, workload, cams,
                                       n_workers=w, budget=budget, res=res)
            result["setup_plus_run_s"] = time.perf_counter() - t0
            runs[str(w)] = result
            print(f"[fleet] {w} worker(s): "
                  f"{result['aggregate_fps']:.2f} req/s, "
                  f"p95 {result['latency_p95_s'] * 1000:.1f} ms, "
                  f"dropped {result['dropped']}, "
                  f"revivals {sum(s['revivals'] for s in result['worker_stats'].values())}")
            if w == args.workers and args.metrics_dump:
                snap = snapshot_json(router.registry,
                                     extra=router.stats())
                with open(args.metrics_dump, "w") as f:
                    json.dump(snap, f, indent=2)
                print(f"[obs] metrics snapshot written to "
                      f"{args.metrics_dump}")
            router.close()

        single, fleet = runs["1"], runs[str(args.workers)]
        speedup = fleet["aggregate_fps"] / single["aggregate_fps"]
        report = {
            "mode": "tiny" if args.tiny else "full",
            "platform": platform,
            "config": shape,
            "scenes": names,
            "one_scene_bytes": one_scene_bytes,
            "per_worker_budget_bytes": budget,
            "budget_scenes": budget_scenes,
            "requests": n_requests,
            "streams": args.streams,
            "zipf_s": args.zipf,
            "res": res,
            "export_s": export_s,
            "runs": runs,
            "fleet_speedup": speedup,
            "notes": "closed-loop zipfian workload; same per-worker "
                     "budget both runs — the single worker thrashes its "
                     "LRU across all scenes while the sharded fleet "
                     "keeps each shard resident (plus real process "
                     "parallelism on multi-core hosts)",
        }
        out = os.path.abspath(args.out)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({k: v for k, v in report.items()
                          if k not in ("config", "notes")}, indent=2))
        print(f"report -> {out}")

        if args.check:
            failures = []
            if speedup < 1.5:
                failures.append(
                    f"fleet speedup {speedup:.2f}x < 1.5x "
                    f"({fleet['aggregate_fps']:.2f} vs "
                    f"{single['aggregate_fps']:.2f} req/s)")
            for w, r in runs.items():
                if r["dropped"]:
                    failures.append(f"{r['dropped']} dropped non-deadline "
                                    f"requests at {w} worker(s)")
                if r["replays"]:
                    failures.append(f"{r['replays']} replays at {w} "
                                    f"worker(s) — no worker should die "
                                    f"in this benchmark")
            if failures:
                print("CHECK FAILED: " + "; ".join(failures))
                sys.exit(1)
            print(f"CHECK OK: fleet speedup {speedup:.2f}x >= 1.5x, "
                  f"zero dropped requests")
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
