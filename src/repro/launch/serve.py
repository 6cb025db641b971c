"""Serving launcher: batched prefill + decode loop (LM) or batched
novel-view rendering (rtnerf).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf \
        --scene lego --views 2 --prune-sparsity 0.9
    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf --demo \
        --scene lego --views 2 --res 64 \
        --prune-sparsity 0.9 --ckpt-dir /tmp/lego-ckpt
    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf --demo \
        --scene lego --res 64 --finetune-steps 200 --finetune-every 50
    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf --demo \
        --scenes lego,chair,mic --res 64 --max-resident-mb 2 \
        --finetune-steps 100
    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf --demo \
        --scenes lego,chair,mic --res 64 --fleet-workers 2 \
        --max-resident-mb 2

rtnerf serves the published field config (`configs/rtnerf.CONFIG`: grid
160, 800x800) unless `--demo` picks the small `demo_config()`. The first
call compiles; JAX's persistent compile cache lives where
JAX_COMPILATION_CACHE_DIR says, else in `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, get_arch, reduced
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models import transformer as tf
from repro.models.common import split_pl
from repro.models.sharding import make_rules
from repro.launch.mesh import make_host_mesh


def enable_compile_cache():
    """Keep JAX's persistent compile cache at one fixed path in the
    checkout, unless JAX_COMPILATION_CACHE_DIR names a directory (JAX reads
    that variable itself). Called by entry points, never on import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            "..", "..", ".."))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))


def nerf_config(args):
    """The field config rtnerf serves: the published one, or the small
    demo shapes with --demo; --max-resident-mb sets the store budget."""
    from repro.configs.base import mib_to_bytes
    from repro.configs.rtnerf import CONFIG, demo_config

    cfg = demo_config() if args.demo else CONFIG
    return dataclasses.replace(
        cfg, max_resident_bytes=mib_to_bytes(args.max_resident_mb))


def serve_lm(args):
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_host_mesh()
    rules = make_rules(mesh)
    key = jax.random.PRNGKey(0)
    params, _ = split_pl(tf.init_model(cfg, key))

    B, P, G = args.batch, args.prompt_len, args.gen
    total = P + G
    batch = {"tokens": jax.random.randint(key, (B, P), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        batch["frontend"] = jnp.zeros((B, cfg.n_frontend_tokens, cfg.d_model),
                                      jnp.bfloat16)
    if cfg.enc_dec:
        batch["enc_frames"] = jax.random.normal(key, (B, P, cfg.d_model),
                                                jnp.bfloat16)

    prefill = jax.jit(build_prefill_step(cfg, rules))
    decode = jax.jit(build_decode_step(cfg, rules, total),
                     static_argnames=())

    t0 = time.time()
    logits, cache = prefill(params, batch)
    # grow caches to the serving horizon (cross-KV at true encoder length)
    shapes, _ = tf.serve_cache_spec(cfg, B, total, enc_len=P)

    def fit(c, s):
        if c.shape == s.shape:
            return c
        pad = [(0, a - b) for a, b in zip(s.shape, c.shape)]
        return jnp.pad(c.astype(s.dtype), pad)
    cache = jax.tree.map(fit, cache, shapes)
    print(f"prefill: {time.time() - t0:.2f}s logits {logits.shape}")

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(G - 1):
        logits, cache = decode(params, tok, jnp.int32(P + i), cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"decoded {B}x{G - 1} tokens in {dt:.2f}s "
          f"({B * (G - 1) / max(dt, 1e-9):.1f} tok/s)")
    print("sample:", toks[0, :12].tolist())


def serve_nerf(args):
    """Streaming multi-view serving from a store of resident compressed
    fields.

    --scenes a,b,c serves several named scenes from ONE process: each is
    restored from its per-scene subdirectory of --ckpt-dir when a
    checkpoint exists (trained once — compressed-native — and saved there
    in encoded form otherwise), registered in the engine's SceneStore, and
    every queued view is rendered by the engine's single jitted
    micro-batched step, grouped per scene at flush time. --max-resident-mb
    bounds the encoded bytes resident at once: cold scenes are LRU-evicted
    to encoded checkpoints and revived transparently when their next
    request arrives. --deadline fails stale requests instead of rendering
    them late. --finetune-steps starts the online fine-tuning service
    (serving.FineTuneLoop): one background trainer PER RESIDENT SCENE
    refreshes its field through the store every --finetune-every steps
    while the request streams keep rendering.
    """
    import contextlib
    import json

    from repro.data import rays as rays_lib
    from repro.obs import (MetricsRegistry, MetricsServer, StatsReporter,
                           snapshot_json)
    from repro.serving import FineTuneLoop, RenderEngine

    scenes = [s for s in args.scenes.split(",") if s] if args.scenes \
        else [args.scene]
    cfg = nerf_config(args)

    # the registry is created BEFORE the engine (which may train scenes for
    # minutes) so the exposition endpoint answers scrapes from the start;
    # the engine and every fine-tune loop record into this same registry
    registry = MetricsRegistry()
    holder = {"engine": None}

    def _extra_stats():
        eng = holder["engine"]
        return eng.stats() if eng is not None else {"phase": "loading"}

    mserver = None
    if args.metrics_port is not None:
        mserver = MetricsServer(registry, port=args.metrics_port,
                                extra=_extra_stats)
        print(f"[obs] metrics: http://127.0.0.1:{mserver.port}/metrics "
              f"(Prometheus) and /metrics.json (snapshot)", flush=True)

    engine = RenderEngine.from_scenes(
        cfg, scenes, ckpt_root=args.ckpt_dir,
        train_steps=args.train_steps, n_views=8, image_hw=args.res,
        prune_sparsity=args.prune_sparsity, encode=not args.dense,
        max_batch_views=args.views,
        auto_flush_interval=(0.25 if args.finetune_steps else None),
        registry=registry)
    holder["engine"] = engine

    reporter = None
    if args.stats_interval:
        def _stats_line():
            s = engine.stats()
            return (f"[obs] views={s['views_served']} fps={s['fps']:.3f} "
                    f"p50={s['latency_p50_s'] * 1e3:.0f}ms "
                    f"p99={s['latency_p99_s'] * 1e3:.0f}ms "
                    f"flushes={s['flushes']} timeouts={s['timeouts']} "
                    f"dropped={s['dropped_pairs']} swaps={s['field_swaps']}")
        reporter = StatsReporter(_stats_line, args.stats_interval)
    for name in scenes:
        s = engine.stats(scene=name)
        print(f"scene '{name}': {s['field_kind']}, "
              f"{s['factor_bytes']:.0f} B factors "
              f"(dense {s['factor_bytes_dense']:.0f} B, "
              f"{s['compression_ratio']:.2f}x)")
    if engine.store.max_resident_bytes:
        print(f"resident budget {engine.store.max_resident_bytes} B, "
              f"resident now: {engine.store.resident_scenes()}")

    loops = []
    if args.finetune_steps:
        # one trainer thread per resident scene, all publishing through
        # the store (ROADMAP "multi-scene fine-tuning")
        loops = [FineTuneLoop.attach(engine.store, name,
                                     steps=args.finetune_steps,
                                     publish_every=args.finetune_every,
                                     n_views=8, image_hw=args.res,
                                     verbose=True).start()
                 for name in scenes]

    gt_scenes = {name: rays_lib.make_scene(name) for name in scenes}
    cams = rays_lib.make_cameras(args.views, args.res, args.res)
    gts = {name: [rays_lib.render_gt(gt_scenes[name], cam) for cam in cams]
           for name in scenes}
    rounds = 1 if not loops else max(args.finetune_rounds, 1)
    # --profile-dir captures a profile of the serving rounds: device ops
    # named by core/pipeline.py's scopes, and the engine's stages as
    # engine.* host annotations on the same clock (repro/obs/tracing.py)
    prof = (jax.profiler.trace(args.profile_dir) if args.profile_dir
            else contextlib.nullcontext())
    failed = 0
    with prof:
        for rnd in range(rounds):
            futures = [(name, engine.submit(cam, gt, scene=name,
                                            deadline_s=args.deadline))
                       for name in scenes
                       for cam, gt in zip(cams, gts[name])]
            for i, (name, fut) in enumerate(futures):
                r = fut.result()
                if r.timed_out:
                    failed += 1
                    print(f"{name} view {i}: TIMED OUT after "
                          f"{r.latency_s:.2f}s")
                    continue
                print(f"{name} view {i}: psnr={r.psnr:.2f} "
                      f"latency={r.latency_s:.2f}s "
                      f"occ_accesses={r.stats['occ_accesses']:.0f} "
                      f"factor_bytes={r.stats['factor_bytes']:.0f}")
    if args.profile_dir:
        print(f"[obs] XLA profile written to {args.profile_dir}")
    if loops:
        for loop in loops:
            loop.join()
        engine.close()
        total_steps = sum(loop.trainer.step_count for loop in loops)
        total_swaps = sum(len(loop.swaps) for loop in loops)
        print(f"fine-tuned {total_steps} steps over {len(loops)} scenes, "
              f"{total_swaps} live swaps "
              f"(max swap {engine.stats()['swap_latency_s_max'] * 1e3:.1f}ms)")
    s = engine.stats()
    devs = jax.devices()
    print(f"served {s['views_served']} views over {s['n_scenes']} scenes "
          f"on {devs[0].platform}/{devs[0].device_kind} x{len(devs)}, "
          f"{s['fps']:.3f} FPS, "
          f"p50={s['latency_p50_s']:.2f}s p95={s['latency_p95_s']:.2f}s, "
          f"ordering-cache hits={s['ordering_cache']['hits']}, "
          f"timeouts={s['timeouts']}, swaps={s['field_swaps']}, "
          f"evictions={s['evictions']}, revivals={s['revivals']}, "
          f"pair_budget={s['pair_budget']} "
          f"(init {s['pair_budget_initial']}, "
          f"{s['pair_budget_resizes']} resizes)")
    br = engine.stage_breakdown()
    if br:
        print("stage breakdown (per request):")
        for stage, d in br.items():
            print(f"  {stage:>15s}  n={d['count']:4d} "
                  f"p50={d['p50_s'] * 1e3:8.2f}ms "
                  f"p99={d['p99_s'] * 1e3:8.2f}ms "
                  f"total={d['total_s']:7.3f}s")
    if args.metrics_dump:
        snap = snapshot_json(registry, extra=s)
        with open(args.metrics_dump, "w") as f:
            json.dump(snap, f, indent=2)
        print(f"[obs] metrics snapshot written to {args.metrics_dump}")
    if reporter is not None:
        reporter.close()
    if mserver is not None:
        mserver.close()
    if failed:
        raise SystemExit(f"{failed} view(s) failed")


def serve_fleet(args):
    """Fleet tier: shard --scenes across --fleet-workers worker processes
    by consistent hashing (serving.FleetRouter).

    Each worker is a full RenderEngine in its own process; scenes are
    trained/restored once in the launcher (same --ckpt-dir contract as the
    single-process path), exported in encoded form, and registered lazily
    on their owning worker. --max-resident-mb applies PER WORKER — the
    point of sharding on a memory-bounded box is that each worker's ~1/K
    shard stays resident instead of one engine LRU-thrashing across all
    scenes. --fleet-replicas R pins the first scene (the designated hot
    scene) on R workers behind one key; the router picks the least-loaded
    replica per request. --deadline, --metrics-port and --metrics-dump
    behave as in the single-process path, with the fleet_* metric
    families layered on top (docs/observability.md).
    """
    import contextlib
    import json
    import shutil
    import tempfile

    from repro.data import rays as rays_lib
    from repro.obs import MetricsRegistry, MetricsServer, snapshot_json
    from repro.serving import FleetRouter, export_scene, prepare_field
    from repro.serving.router import require_host_processes

    require_host_processes()
    if args.finetune_steps:
        raise SystemExit(
            "--fleet-workers does not combine with --finetune-steps yet: "
            "fleet workers own their engines, so the fine-tune loop would "
            "train a field no worker serves (ROADMAP: fleet fine-tuning)")
    scenes = [s for s in args.scenes.split(",") if s] if args.scenes \
        else [args.scene]
    cfg = nerf_config(args)

    registry = MetricsRegistry()
    holder = {"router": None}

    def _extra_stats():
        r = holder["router"]
        return r.stats() if r is not None else {"phase": "loading"}

    mserver = None
    if args.metrics_port is not None:
        mserver = MetricsServer(registry, port=args.metrics_port,
                                extra=_extra_stats)
        print(f"[obs] metrics: http://127.0.0.1:{mserver.port}/metrics "
              f"(Prometheus) and /metrics.json (snapshot)", flush=True)

    # Train/restore in the launcher (one jit, reuses --ckpt-dir exactly
    # like the single-process path), then export each scene's encoded
    # streams + cubes once; workers register from these paths, so every
    # replica and every post-crash re-registration serves the identical
    # representation.
    export_root = tempfile.mkdtemp(prefix="repro-fleet-")
    paths = {}
    for name in scenes:
        ckpt = os.path.join(args.ckpt_dir, name) if args.ckpt_dir else None
        field = prepare_field(cfg, name, ckpt_dir=ckpt,
                              train_steps=args.train_steps, n_views=8,
                              image_hw=args.res)
        if args.prune_sparsity > 0.0:
            field = field.prune(sparsity=args.prune_sparsity)
        paths[name] = export_scene(os.path.join(export_root, name),
                                   field, cfg=cfg, scene=name)

    router = FleetRouter(
        cfg, paths, n_workers=args.fleet_workers,
        engine_kwargs=dict(max_batch_views=args.views),
        registry=registry)
    holder["router"] = router
    try:
        for name in scenes:
            print(f"scene '{name}' -> worker {router.owner_of(name)}")
        if args.fleet_replicas > 1:
            hot = scenes[0]
            router.set_replicas(hot, args.fleet_replicas)
            print(f"hot scene '{hot}' replicated on "
                  f"{router.replica_workers(hot)}")

        gt_scenes = {name: rays_lib.make_scene(name) for name in scenes}
        cams = rays_lib.make_cameras(args.views, args.res, args.res)
        gts = {name: [rays_lib.render_gt(gt_scenes[name], cam)
                      for cam in cams] for name in scenes}
        prof = (jax.profiler.trace(args.profile_dir) if args.profile_dir
                else contextlib.nullcontext())
        failed = 0
        with prof:
            futures = [(name, router.submit(cam, gt, scene=name,
                                            deadline_s=args.deadline))
                       for name in scenes
                       for cam, gt in zip(cams, gts[name])]
            for i, (name, fut) in enumerate(futures):
                r = fut.result()
                if r.timed_out:
                    failed += 1
                    print(f"{name} view {i}: TIMED OUT after "
                          f"{r.latency_s:.2f}s")
                    continue
                print(f"{name} view {i}: psnr={r.psnr:.2f} "
                      f"latency={r.latency_s:.2f}s worker={r.worker}"
                      f"{' (replayed)' if r.replayed else ''}")
        if args.profile_dir:
            print(f"[obs] XLA profile written to {args.profile_dir}")

        s = router.stats()
        print(f"fleet: {s['results_total']} results over "
              f"{len(scenes)} scenes / {s['workers_alive']} workers, "
              f"p95={s['latency_p95_s']:.2f}s, "
              f"timeouts={s['timeouts_total']}, "
              f"replays={s['replays_total']}, "
              f"deaths={s['worker_deaths']}, "
              f"routing v{s['routing_version']}")
        for wname, ws in sorted(s["workers"].items()):
            print(f"  {wname}: views={ws.get('views_served', 0)} "
                  f"fps={ws.get('fps', 0.0):.3f} "
                  f"resident={ws.get('resident_scenes', [])} "
                  f"evictions={ws.get('evictions', 0)} "
                  f"revivals={ws.get('revivals', 0)}")
        if args.metrics_dump:
            snap = snapshot_json(registry, extra=s)
            with open(args.metrics_dump, "w") as f:
                json.dump(snap, f, indent=2)
            print(f"[obs] metrics snapshot written to {args.metrics_dump}")
    finally:
        router.close()
        shutil.rmtree(export_root, ignore_errors=True)
        if mserver is not None:
            mserver.close()
    if failed:
        raise SystemExit(f"{failed} view(s) failed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=sorted(ARCHS) + ["rtnerf"])
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--scene", default="lego")
    ap.add_argument("--scenes", default=None,
                    help="rtnerf only: comma-separated scene list to serve "
                         "from one process (e.g. lego,chair,mic); overrides "
                         "--scene. Each scene checkpoints under its own "
                         "subdirectory of --ckpt-dir")
    ap.add_argument("--max-resident-mb", type=float, default=None,
                    help="rtnerf only: device-memory budget (MiB) for "
                         "resident encoded fields across scenes; cold "
                         "scenes are LRU-evicted to encoded checkpoints "
                         "and revived on their next request (default: "
                         "unlimited)")
    ap.add_argument("--fleet-workers", type=int, default=0,
                    help="rtnerf only: serve through K worker processes "
                         "sharded by consistent hashing instead of one "
                         "in-process engine (serving.FleetRouter); "
                         "--max-resident-mb then applies per worker "
                         "(0 = single-process path)")
    ap.add_argument("--fleet-replicas", type=int, default=1,
                    help="rtnerf only, with --fleet-workers: replicate the "
                         "first --scenes entry (the hot scene) on this many "
                         "workers behind one key; the router load-balances "
                         "across the replicas")
    ap.add_argument("--demo", action="store_true",
                    help="rtnerf only: serve the small demo_config() field "
                         "instead of the published CONFIG widths")
    ap.add_argument("--views", type=int, default=2)
    ap.add_argument("--res", type=int, default=None,
                    help="rtnerf only: view height/width in pixels "
                         "(default: the config's image_hw, 800)")
    ap.add_argument("--train-steps", type=int, default=200)
    ap.add_argument("--dense", action="store_true",
                    help="rtnerf only: serve the raw factor arrays instead "
                         "of the hybrid bitmap/COO compressed stream "
                         "(Sec. 4.2.2; replaces the removed --field-mode)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="rtnerf only: per-request deadline in seconds; "
                         "stale requests fail with a timeout result "
                         "instead of rendering late")
    ap.add_argument("--finetune-steps", type=int, default=0,
                    help="rtnerf only: run the online fine-tuning service "
                         "for this many background training steps while "
                         "serving (0 = off); refreshed fields are published "
                         "live via swap_field")
    ap.add_argument("--finetune-every", type=int, default=50,
                    help="rtnerf only: publish the refreshed field to the "
                         "running engine every N fine-tune steps")
    ap.add_argument("--finetune-rounds", type=int, default=3,
                    help="rtnerf only: how many passes over the view set "
                         "to stream while the fine-tuner runs")
    ap.add_argument("--prune-sparsity", type=float, default=0.0,
                    help="rtnerf only: magnitude-prune factors to this "
                         "sparsity before serving (0 = training prune only)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="rtnerf only: expose the metrics registry over "
                         "HTTP on 127.0.0.1:<port> (/metrics Prometheus "
                         "text, /metrics.json snapshot); 0 picks an "
                         "ephemeral port (printed at startup)")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    help="rtnerf only: print a one-line serving summary "
                         "every N seconds while serving (0 = off)")
    ap.add_argument("--metrics-dump", default=None,
                    help="rtnerf only: write the final metrics snapshot "
                         "(JSON, schema repro.obs/v1) to this path on exit "
                         "— the input of scripts/obs_report.py")
    ap.add_argument("--profile-dir", default=None,
                    help="rtnerf only: capture an XLA profiler trace of "
                         "the serving rounds into this directory "
                         "(jax.profiler.trace; named scopes from "
                         "core/pipeline.py tag the pipeline stages, "
                         "engine.* annotations the engine's)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="rtnerf only: restore trained fields from "
                         "per-scene subdirectories of this root when "
                         "checkpoints exist; otherwise train once and save "
                         "there (repeated serves reuse them instead of "
                         "retraining)")
    args = ap.parse_args()
    if args.fleet_workers and args.arch != "rtnerf":
        ap.error("--fleet-workers requires --arch rtnerf")
    enable_compile_cache()
    if args.arch == "rtnerf":
        if args.res is None:
            args.res = nerf_config(args).image_hw
        if args.fleet_workers:
            serve_fleet(args)
        else:
            serve_nerf(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
