"""Bring-up smoke for the compressed-field serving path on a TPU chip.

    python chip_smoke.py              # one chip, published widths
    python chip_smoke.py --chips 4    # ray-sharded engine over every chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

One chip: `NerfTrainer` trains `configs/rtnerf.CONFIG` (grid 160, R 16/48,
app_dim 27, MLP 128, max_cubes 8192) on the procedural "lego" scene for
TRAIN_STEPS dense steps, the field is pruned to 0.9 sparsity and
hybrid-encoded, and `RenderEngine` serves RES x RES views from it, then
the same cameras from the pruned dense field it encodes (`decode()` is
the exact inverse). Before serving, uniform-sample renders score the
served cameras against ground truth: the unpruned and the pruned field
through the training renderer, and the pruned field through the uniform
baseline pipeline with the served occupancy cubes. The PSNR against
ground truth so splits into training, pruning and serving. Checks: every
image is finite; the unpruned field beats a blank white canvas by
TRAINED_GAIN_DB (training learned the scene); hybrid agrees with dense
(HYBRID_DENSE_MIN_PSNR); each served hybrid view scores at least the
baseline less SERVE_MARGIN_DB (encoding and the RT-NeRF pipeline keep
what the pruned field and its cubes hold).

Views are RES x RES, not CONFIG's 800x800: on one v5e a render-step scan
step takes ~21 ms at CONFIG widths (hybrid at any pair budget, dense at the
default one), and an 800x800 view is 157 ray chunks x 1024 steps, about
55 minutes. A RES x RES view is one ray chunk.

--chips N: one process builds an engine meshed over every device and an
engine on one device, serves the same view of a seeded random field (no
training) from both, prints the ray chunk's sharding (which must not be
replicated) and checks that the images agree (SHARDED_MAX_ABS).

Every time printed is a single run, not a benchmark. On success the last
line of stdout is {"ok": true, "device": {...}}; a failed check exits
non-zero without it. Without a TPU the script stops at once; --tiny runs
every phase on the CPU at a small size and then fails that check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SCENE = "lego"
SEED = 0
RES = 64                       # one 4096-ray chunk per view
# Dense steps (compressed=False): the compressed-native step after a
# re-encode needs 21.4 GB at CONFIG widths.
TRAIN_STEPS = 250
TRAIN_VIEWS = 8
SPARSITY = 0.9
N_VIEWS = 2
HYBRID_DENSE_MIN_PSNR = 40.0   # dB between the hybrid and dense images
TRAINED_GAIN_DB = 10.0         # dB, unpruned field over a blank canvas, vs
                               # ground truth (training renderer)
SERVE_MARGIN_DB = 3.0          # dB a served hybrid view may fall below the
                               # uniform baseline on the same pruned field
                               # and cubes: room for the octant cube
                               # order, which is only coarsely
                               # front-to-back
SHARDED_MAX_ABS = 2e-3         # max |pixel| difference, N devices vs one
NOTE = "(single run, not a benchmark)"


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str):
    print(f"check {'passed' if ok else 'FAILED'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phase:
    """Prints a phase's wall seconds, the backend compile seconds inside
    it and the device's peak memory so far."""

    def __init__(self, name, jax, clog):
        self.name, self.jax, self.clog = name, jax, clog

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clog.compile_s
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        stats = self.jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        peak_s = f"{peak / 2**30:.3f} GiB" if peak is not None else \
            "not reported"
        print(f"[{self.name}] {time.perf_counter() - self.t0:.2f}s wall, "
              f"{self.clog.compile_s - self.c0:.2f}s backend compile, "
              f"peak device memory {peak_s} {NOTE}", flush=True)
        return False


def serve_views(engine, cams, gts, label):
    """Submit the views one at a time through the engine; returns the
    ViewResults. The first includes the render step's compile."""
    out = []
    for i, (cam, gt) in enumerate(zip(cams, gts)):
        r = engine.submit(cam, gt, scene=SCENE).result()
        check(not r.timed_out, f"{label} view {i} rendered")
        psnr = "" if r.psnr is None else \
            f", psnr vs ground truth {r.psnr:.2f} dB"
        print(f"  {label} view {i}: latency {r.latency_s:.3f}s"
              f"{' (includes compile)' if i == 0 else ''}{psnr} {NOTE}",
              flush=True)
        out.append(r)
    s = engine.stats()
    print(f"  {label}: dispatch_path={engine.field.dispatch_path()}, "
          f"views served {s['views_served']}, "
          f"dropped_pairs={s['dropped_pairs']}, "
          f"pair budget {s['pair_budget']} (initial "
          f"{s['pair_budget_initial']}, {s['pair_budget_resizes']} "
          f"resizes), ray_chunk {s['ray_chunk']}, "
          f"devices {s['n_devices']}", flush=True)
    return out


def pair_psnr(a, b):
    import numpy as np

    mse = float(np.mean(np.square(np.asarray(a) - np.asarray(b))))
    return float("inf") if mse == 0.0 else -10.0 * np.log10(mse)


def one_chip(cfg, res, jax, clog):
    import numpy as np

    from repro.core import occupancy as occ_lib
    from repro.core import rendering
    from repro.core.train import NerfTrainer
    from repro.data import rays as rays_lib
    from repro.serving import RenderEngine

    with Phase("train", jax, clog):
        trainer = NerfTrainer(cfg, SCENE, n_views=TRAIN_VIEWS,
                              image_hw=res, seed=SEED, compressed=False)
        for _ in range(TRAIN_STEPS):
            rec = trainer.step()
        print(f"  {TRAIN_STEPS} steps, last batch psnr {rec['psnr']:.2f} "
              f"dB", flush=True)
    with Phase("prune+encode", jax, clog):
        full = trainer.snapshot()
        pruned = full.prune(sparsity=SPARSITY)
        hybrid = pruned.encode()
        cubes = occ_lib.extract_cubes(
            occ_lib.build_occupancy(pruned, cfg), cfg)
        print(f"  {hybrid.factor_bytes()} B of encoded factors "
              f"({hybrid.compression_ratio():.2f}x vs dense), "
              f"{cubes.count} occupied cubes of "
              f"{cfg.cube_grid_res ** 3} (max_cubes {cfg.max_cubes})",
              flush=True)
    with Phase("ground truth + reference renders", jax, clog):
        cams = rays_lib.make_cameras(N_VIEWS, res, res)
        scene = rays_lib.make_scene(SCENE)
        gts = [np.asarray(rays_lib.render_gt(scene, c)) for c in cams]
        # uniform samples along each ray; with cubes it queries their
        # occupancy grid (the paper's baseline pipeline), without it is
        # the training renderer
        uniform = jax.jit(lambda f, c, o, d: rendering.render_uniform(
            f, cfg, c, o, d, use_occupancy=c is not None)[0])
        ref = {"blank": [], "unpruned": [], "pruned": [], "baseline": []}
        for cam, gt in zip(cams, gts):
            o, d = rendering.camera_rays(cam)
            for key, img in (("blank", np.ones_like(gt)),
                             ("unpruned", uniform(full, None, o, d)),
                             ("pruned", uniform(pruned, None, o, d)),
                             ("baseline", uniform(pruned, cubes, o, d))):
                ref[key].append(pair_psnr(np.clip(img, 0, 1), gt))
        print("  vs ground truth (dB per view): " + "; ".join(
            f"{k} {', '.join(f'{v:.2f}' for v in vals)}"
            for k, vals in ref.items()) + "  [unpruned/pruned: training "
            "renderer; baseline: uniform pipeline on the pruned field and "
            "the served cubes]", flush=True)
    for i in range(N_VIEWS):
        floor = ref["blank"][i] + TRAINED_GAIN_DB
        check(ref["unpruned"][i] >= floor,
              f"view {i} unpruned field {ref['unpruned'][i]:.2f} dB >= "
              f"{floor:.2f} dB, blank canvas + {TRAINED_GAIN_DB}")

    served = {}
    for kind, f, encode in (("hybrid", hybrid, True),
                            ("dense", pruned, False)):
        with Phase(f"serve {kind}", jax, clog):
            with RenderEngine(cfg, f, cubes, scene_name=SCENE,
                              encode=encode) as engine:
                served[kind] = serve_views(engine, cams, gts, kind)

    for i in range(N_VIEWS):
        h, d = served["hybrid"][i], served["dense"][i]
        check(bool(np.isfinite(h.img).all() and np.isfinite(d.img).all()),
              f"view {i} images finite")
        agree = pair_psnr(h.img, d.img)
        max_abs = float(np.max(np.abs(h.img - d.img)))
        check(agree >= HYBRID_DENSE_MIN_PSNR,
              f"view {i} hybrid vs dense {agree:.2f} dB >= "
              f"{HYBRID_DENSE_MIN_PSNR} dB (max |diff| {max_abs:.2e})")
        floor = ref["baseline"][i] - SERVE_MARGIN_DB
        check(h.psnr >= floor,
              f"view {i} served hybrid vs ground truth {h.psnr:.2f} dB >= "
              f"{floor:.2f} dB, baseline - {SERVE_MARGIN_DB} "
              f"(dense {d.psnr:.2f} dB)")


def sharded(cfg, res, jax, clog):
    import numpy as np

    from repro.core import distributed
    from repro.core import field as field_lib
    from repro.core import occupancy as occ_lib
    from repro.core import tensorf
    from repro.data import rays as rays_lib
    from repro.serving import RenderEngine

    devs = jax.devices()
    check(len(devs) > 1, f"{len(devs)} devices to shard over")
    with Phase("seeded field", jax, clog):
        field = field_lib.DenseField(
            tensorf.init_field(cfg, jax.random.PRNGKey(SEED)), cfg
        ).prune(sparsity=SPARSITY).encode()
        cubes = occ_lib.extract_cubes(
            occ_lib.build_occupancy(field, cfg), cfg)
    # one view: at CONFIG widths a view costs each engine ~30 s
    cams = rays_lib.make_cameras(N_VIEWS, res, res)[:1]
    mesh_one = jax.sharding.Mesh(np.asarray(devs[:1]).reshape(1, 1),
                                 ("data", "model"))
    imgs = {}
    for label, mesh in ((f"{len(devs)} devices", None), ("1 device",
                                                         mesh_one)):
        with Phase(f"serve {label}", jax, clog):
            with RenderEngine(cfg, field, cubes, scene_name=SCENE,
                              mesh=mesh) as engine:
                sh = distributed.ray_sharding(engine.rules,
                                              engine.ray_chunk)
                print(f"  ray chunk sharding: {sh.spec} over "
                      f"{dict(engine.rules.mesh.shape)}", flush=True)
                if mesh is None:
                    check(not sh.is_fully_replicated,
                          "ray chunk is sharded, not replicated")
                imgs[label] = [r.img for r in serve_views(
                    engine, cams, [None] * len(cams), label)]
    many, one = imgs.values()
    for i in range(len(cams)):
        check(bool(np.isfinite(many[i]).all()), f"view {i} finite")
        diff = float(np.max(np.abs(many[i] - one[i])))
        check(diff <= SHARDED_MAX_ABS,
              f"view {i} {len(devs)} devices vs 1: max |diff| {diff:.2e} "
              f"<= {SHARDED_MAX_ABS}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: train and serve on one chip; N > 1: compare "
                         "an engine meshed over all N devices with one on "
                         "a single device (no other phase)")
    ap.add_argument("--tiny", action="store_true",
                    help="demo_config(tiny=True) at 32x32: the CPU "
                         "rehearsal; it ends failing the platform check")
    args = ap.parse_args()

    import jax

    from repro.configs.rtnerf import CONFIG, demo_config
    from repro.launch.serve import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"FAILED: JAX found no TPU (platform {dev.platform}); "
              f"use --tiny for a CPU rehearsal", file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"FAILED: --chips {args.chips} but JAX sees {len(devs)} "
              f"devices", file=sys.stderr)
        return 1
    enable_compile_cache()
    clog = CompileLog(jax)
    cfg = demo_config(tiny=True) if args.tiny else CONFIG
    res = 32 if args.tiny else RES
    print(f"device {dev.platform}/{dev.device_kind} x{len(devs)}, "
          f"jax {jax.__version__}, grid {cfg.grid_res}, R "
          f"{cfg.r_sigma}/{cfg.r_color}, app_dim {cfg.app_dim}, MLP "
          f"{cfg.mlp_hidden}, {res}x{res}, compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        (sharded if args.chips > 1 else one_chip)(cfg, res, jax, clog)
    except CheckFailed:
        return 1
    print(f"total {time.perf_counter() - t0:.2f}s, backend compile "
          f"{clog.compile_s:.2f}s, persistent cache {clog.hits} hits / "
          f"{clog.misses} misses {NOTE}", flush=True)
    if not on_tpu:
        print(f"FAILED: platform check: ran on {dev.platform}, not tpu "
              f"(CPU rehearsal only)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
