"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives the system's serving entry, `RenderEngine.submit(...)`
then `.result()`, as one closed-loop client: each view is submitted when
the previous one has returned. With `--trace 1` the window is one whole
view under the profiler, and the run reports the cell's per-layer
metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

import chip_smoke
from bench import compare, device, geometry, inputs, shapes, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_COMPARED = 8          # views checked against the reference per run


class CompileLog(chip_smoke.CompileLog):
    """The program's log of compiles and cache hits, with the time each
    backend compile ended."""

    def __init__(self):
        import jax
        self.ends: List[float] = []
        super().__init__(jax)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.ends.append(time.perf_counter())
        super()._duration(event, secs, **kw)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.ends)


@dataclasses.dataclass
class View:
    pose: traffic.Pose
    t_done: float
    img: np.ndarray
    latency_s: float
    render_s: float
    timed_out: bool


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py`, loaded by file path and registered in
    `sys.modules` (as dataclasses and pickling look a module up there)."""
    path = os.path.join(ROOT, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_arch(name: str):
    """The module of a field architecture (`bench/archs/<name>.py`): its
    weights, program objects, reference field, work and trace scopes."""
    return load_module("archs", name)


def load_cell(name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix, its
    configuration's architecture module)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    return bench, cell, conf, mix, load_arch(conf["arch"])


def enable_compile_cache():
    """The program's persistent compile cache (the checkout's fixed
    `.jax_cache`, unless JAX_COMPILATION_CACHE_DIR names one), holding
    every program however small or quick to compile."""
    import jax
    from repro.launch.serve import enable_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def set_precision(conf: Dict):
    """The matmul precision the configuration states, for the whole
    process (on a TPU, JAX's default runs a float32 matmul as one bfloat16
    pass)."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      conf.get("matmul_precision"))


def make_mesh(devs):
    import jax
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(len(devs), 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_engine(cfg, field, cubes, conf: Dict, scene: str, mesh):
    """The served scene behind the engine's defaults: ray chunk 4096, cube
    chunk 8, adaptive pair budget, octant ordering."""
    from repro.serving.engine import RenderEngine
    return RenderEngine(cfg, field, cubes, scene_name=scene,
                        encode=bool(conf["encode"]), mesh=mesh)


def camera(pose: traffic.Pose):
    import jax.numpy as jnp
    from repro.core.rendering import Camera
    return Camera(jnp.asarray(pose.c2w), jnp.asarray(pose.origin),
                  pose.focal, pose.h, pose.w)


def device_arrays(obj) -> List:
    """Every distinct device array reachable from `obj` through containers,
    named tuples and object attributes."""
    import jax
    seen, found, stack = set(), {}, [obj]
    while stack:
        o = stack.pop()
        if id(o) in seen or o is None or isinstance(
                o, (str, bytes, int, float, bool, np.ndarray)):
            continue
        seen.add(id(o))
        if isinstance(o, jax.Array):
            found[id(o)] = o
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif hasattr(o, "__dict__") and not isinstance(o, type):
            stack.extend(vars(o).values())
    return list(found.values())


def scene_bytes(engine, scene: str) -> int:
    """Bytes of every device array the engine holds for the served scene:
    field, MLP, basis, cubes, ordering schedules."""
    snap = engine.store.snapshot(scene)
    return int(sum(a.nbytes for a in device_arrays(snap)))


def serve(engine, pose: traffic.Pose) -> View:
    import jax
    with jax.profiler.TraceAnnotation("bench.submit"):
        fut = engine.submit(camera(pose))
    with jax.profiler.TraceAnnotation("bench.result"):
        res = fut.result()
    td = time.perf_counter()
    render_s = 0.0
    if res.trace is not None:
        render_s = sum(s["dur_s"] for s in res.trace["stages"]
                       if s["name"] == "render")
    return View(pose, td, res.img, res.latency_s, render_s,
                bool(res.timed_out or res.img is None))


def pair_budget(engine) -> int:
    return int(engine.stats()["pair_budget"])


def warm_up(engine, mix: Dict, centers: np.ndarray, clog: CompileLog,
            log) -> int:
    """Serve the mix's fixed warm-up views until the adaptive pair budget
    has settled (at most `warmup_max_views`): a view left it unchanged and
    either filled a quarter of it or more, which clears the engine's count
    of low-occupancy views (it shrinks only after three in a row), or it
    has stayed put for three views in a row. The first view compiles the
    render step, or loads it from the cache; a resize rebuilds it."""
    last, still, n = pair_budget(engine), 0, 0
    for pose in traffic.warmup_poses(mix, centers):
        if n >= mix["warmup_max_views"]:
            break
        t0 = time.perf_counter()
        v = serve(engine, pose)
        n += 1
        b = pair_budget(engine)
        fill = float(engine.stats()["pair_occupancy_last"])
        still = still + 1 if b == last else 0
        log(f"warm-up view {n}: {v.latency_s:.3f}s, pair budget {last} -> "
            f"{b}, filled {fill:.3f}, "
            f"{clog.between(t0, time.perf_counter())} compiles")
        last = b
        if still >= 3 or (still and fill >= 0.25):
            break
    return n


def load_reader(name: str):
    return load_module("metrics", name).read


def read_counters(engine) -> Dict[str, float]:
    """Every counter of the program's registry, by flat name."""
    snap = engine.store.metrics.snapshot()["counters"]
    return {k: v["value"] for k, v in snap.items()}


def cell_metrics(bench: Dict, cell: Dict, kind: str) -> List[Dict]:
    """The cell's end-to-end or per-layer metrics, as BENCHMARK.json lists
    them."""
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    e2e = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in e2e]


@dataclasses.dataclass
class Window:
    views: List[View]
    t0: float                # first submit
    t_end: float             # the last view's result
    compiles: int            # backend compiles between the two
    counters: Dict[str, float]   # the program's counters' change over it


def serve_window(engine, mix: Dict, centers: np.ndarray, seed: int,
                 seconds: float, trace_dir: Optional[str],
                 clog: CompileLog) -> Window:
    """The closed loop: the seed's passes (`traffic.passes`) for
    `seconds`, a pass started only while the last one's length would end
    it inside them (the first always), every view of it served, waited
    for and counted; or the first view alone under the profiler when
    `trace_dir` is given."""
    import jax
    views: List[View] = []
    last_pass_s = 0.0
    before = read_counters(engine)
    t0 = time.perf_counter()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    for batch in traffic.passes(mix, centers, seed):
        if views and (trace_dir or time.perf_counter()
                      + last_pass_s > t0 + seconds):
            break
        served = [serve(engine, pose)
                  for pose in (batch[:1] if trace_dir else batch)]
        last_pass_s = sum(v.latency_s for v in served)
        views += served
    if trace_dir:
        jax.profiler.stop_trace()
    t_end = views[-1].t_done
    after = read_counters(engine)
    return Window(views, t0, t_end, clog.between(t0, t_end),
                  {k: v - before.get(k, 0.0) for k, v in after.items()})


def check(views: List[View], arch, params, w: Dict, centers: np.ndarray,
          chunk: int, seed: int, limits: Dict[str, float]):
    """(correct, [(number, reading, limit)], hits by view index): a sample
    of the served views drawn from the seed, the slowest among them,
    against the reference."""
    from bench import reference
    rng = np.random.default_rng(inputs.seed_words(seed,
                                                  traffic.SAMPLE_STREAM))
    pick = sorted(range(len(views)), key=lambda i: -views[i].latency_s)[:1]
    rest = [i for i in range(len(views)) if i not in pick]
    pick += [int(i) for i in rng.permutation(rest)[:MAX_COMPARED - 1]]
    imgs, refs, hits_of = [], [], {}
    for i in sorted(pick):
        ro, rd = views[i].pose.rays()
        hits_of[i] = geometry.hits(w, centers, ro, rd, chunk)
        refs.append(reference.render(arch.reference_field, params, w,
                                     hits_of[i], ro, rd))
        imgs.append(views[i].img)
    correct, rows = compare.judge(compare.readings(imgs, refs), limits)
    return correct and not any(v.timed_out for v in views), rows, hits_of


def end_to_end(win: Window, s_bytes: int, setup_s: float) -> Dict:
    d = win.views
    return {
        "rays_per_s": sum(v.pose.n_rays for v in d) / (win.t_end - win.t0),
        "view_ms_p95": float(np.percentile([v.latency_s * 1e3 for v in d],
                                           95)),
        "scene_bytes": float(s_bytes),
        "setup_s": setup_s,
    }


def per_layer(bench, cell, arch, win: Window, summary, peak, n_chips: int,
              w: Dict, hits) -> Dict:
    """The cell's per-layer metrics from its readers; a reader that finds
    nothing to read leaves its metric out."""
    ops = arch.ops_per_sample(w)
    ctx = {
        "views": win.views, "compiles_in_window": win.compiles,
        "window_s": win.t_end - win.t0, "trace": summary, "peak": peak,
        "chips": n_chips, "widths": w, "counters": win.counters,
        "required_samples": shapes.required_samples(w, hits),
        "ops_per_sample": sum(ops.values()),
        "field_ops_per_sample": sum(ops.values()) - ops["composite"],
        "bytes_per_view": arch.bytes_per_view(w, win.views[0].pose.n_rays),
    }
    out = {}
    for m in cell_metrics(bench, cell, "per_layer"):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t_start: Optional[float] = None,
        out=sys.stdout) -> int:
    """One run of one cell; prints its result line on `out`. Returns the
    exit code: 0 with a result, non-zero with none."""
    t_start = time.perf_counter() if t_start is None else t_start

    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    bench, cell, conf, mix, arch = load_cell(workload)
    w = dict(conf["field"])
    if rehearse:
        w.update(arch.rehearsal_widths())
        conf = dict(conf, field=w)

    import jax
    import jax.numpy as jnp
    enable_compile_cache()
    set_precision(conf)
    try:
        devs = device.devices(cell["chips"], rehearse)
    except device.NoChip as e:
        log(f"refused: {e}")
        return 3
    desc = device.describe(devs)
    log(f"device: platform {desc['platform']}, kind {desc['kind']}, "
        f"count {desc['count']}")
    peak = None if rehearse else device.peaks(ROOT, desc["kind"])
    limits = compare.load_limits(ROOT, workload)
    clog = CompileLog()

    from repro.core import occupancy as occ_lib

    params = arch.make_weights(conf, seed)
    jax.block_until_ready(params)
    cfg, field = arch.build(conf, w, params)
    occ = inputs.occupancy(conf)
    centers = inputs.cube_centers(conf, occ)
    cubes = occ_lib.extract_cubes(jnp.asarray(occ), cfg)
    scene = conf["scene"]["name"]
    engine = make_engine(cfg, field, cubes, conf, scene, make_mesh(devs))
    log(f"scene {scene}: {len(centers)} cubes; field "
        f"{engine.stats()['field_kind']}, dispatch "
        f"{engine.field.dispatch_path()}; built at "
        f"{time.perf_counter() - t_start:.3f}s")
    n_warm = warm_up(engine, mix, centers, clog, log)
    s_bytes = scene_bytes(engine, scene)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s ({n_warm} warm-up views, pair budget "
        f"{pair_budget(engine)}, backend compile {clog.compile_s:.3f}s, "
        f"cache {clog.hits} hits / {clog.misses} misses)")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    win = serve_window(engine, mix, centers, seed, seconds, trace_dir, clog)
    t_closed = time.perf_counter()
    log(f"window: {len(win.views)} views in {win.t_end - win.t0:.3f}s; "
        f"latencies "
        f"{', '.join(f'{v.latency_s:.3f}' for v in win.views)}s; "
        f"{win.compiles} compiles inside; pair budget {pair_budget(engine)}")
    mem = device.memory_peak_bytes(devs)
    chunk = engine.cube_chunk
    engine.close()                   # the reference runs with it freed
    del engine, field
    gc.collect()

    t_r0 = time.perf_counter()
    correct, rows, hits_of = check(win.views, arch, params, w, centers,
                                   chunk, seed, limits)
    log(f"reference: {len(hits_of)} views in "
        f"{time.perf_counter() - t_r0:.3f}s")

    result = {"correct": bool(correct), "attempted": len(win.views),
              "failed": int(sum(v.timed_out for v in win.views)),
              "metrics": {},
              "device": dict(desc, memory_peak_bytes=int(mem))}
    if not trace:
        values = end_to_end(win, s_bytes, setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, cell, "end_to_end")}
    else:
        from bench import trace as trace_lib
        summary = trace_lib.reduce_dir(trace_dir, arch.SCOPES)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = per_layer(bench, cell, arch, win, summary, peak,
                                      len(devs), w, hits_of[0])
        if summary is not None:
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
    log(f"metrics: {json.dumps(result['metrics'])}")
    if rehearse:
        # a CPU run's times are not device metrics: none are reported
        result["metrics"] = {}
        result["device"].pop("busy_s", None)
        result["device"].pop("window_s", None)
        result.pop("breakdown", None)
        result["rehearsal"] = True
    result["check"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    log(f"run {time.perf_counter() - t_start:.3f}s in all (window closed "
        f"at {t_closed - t_start:.3f}s)")
    for k, v, lim in rows:
        print(f"check: {k} {v!r} <= {lim!r}", file=sys.stderr, flush=True)
    print(f"check: correct {str(correct).lower()}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0
