"""The ray renderer's work counters against a numpy recount, and the
engine's registry counters against the renderer's own `aux`."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import pipeline, rendering, tensorf
from repro.data import rays as rays_lib
from repro.serving import RenderEngine
from repro.serving.engine import RENDER_COUNTERS

# term_eps 0: no ray terminates early, so every hit is a numpy-countable one
CFG = NeRFConfig(grid_res=24, occ_res=24, cube_size=4, max_cubes=256,
                 r_sigma=4, r_color=8, app_dim=8, mlp_hidden=16,
                 max_samples_per_ray=64, train_rays=256, term_eps=0.0)
CHUNK = 8


@pytest.fixture(scope="module")
def scene():
    params = tensorf.init_field(CFG, jax.random.PRNGKey(0))
    field = field_lib.DenseField(params, CFG).prune(sparsity=0.9)
    occ = occ_lib.build_occupancy(field, CFG, sigma_thresh=0.01)
    cubes = occ_lib.extract_cubes(occ, CFG)
    assert 0 < cubes.count < CFG.max_cubes    # some slots stay invalid
    return field, cubes


def rungs_of(budget):
    """The renderer's rung rule restated: budget/16, budget/4, budget, each
    at least min(budget, 128)."""
    floor = min(budget, 128)
    return sorted({max(budget // 16, floor), max(budget // 4, floor),
                   budget})


def step_hits(centers, valid, ro, rd):
    """Per scan step: (valid cubes, flat hit mask, t0, t1) of the line-slab
    test, in float32 numpy as the renderer computes them."""
    half = np.float32(CFG.cube_world() / 2.0)
    near = np.float32(CFG.near)
    n_steps = math.ceil(len(valid) / CHUNK)
    pad = n_steps * CHUNK - len(valid)
    centers = np.concatenate([centers, np.zeros((pad, 3), np.float32)])
    valid = np.concatenate([valid, np.zeros(pad, bool)])
    safe_d = np.where(np.abs(rd) < 1e-9, np.float32(1e-9), rd)
    for s in range(n_steps):
        ctr = centers[s * CHUNK:(s + 1) * CHUNK]
        vld = valid[s * CHUNK:(s + 1) * CHUNK]
        ta = (ctr[:, None] - half - ro[None]) / safe_d[None]
        tb = (ctr[:, None] + half - ro[None]) / safe_d[None]
        t0 = np.max(np.minimum(ta, tb), axis=-1)
        t1 = np.min(np.maximum(ta, tb), axis=-1)
        hit = ((t1 > t0) & (t1 > near) & vld[:, None]).reshape(-1)
        yield vld, hit, t0.reshape(-1), t1.reshape(-1)


def recount(centers, valid, ro, rd, budget):
    """The renderer's counters from the ordered cube arrays and the
    line-slab hits: a step with no hit evaluates nothing, any other the
    smallest rung that holds min(hits, budget)."""
    near = np.float32(CFG.near)
    delta = np.float32(pipeline.step_world(CFG))
    ns = pipeline.samples_per_segment(CFG)
    rungs = rungs_of(budget)
    out = dict.fromkeys(("scan_steps", "live_steps", "eval_steps",
                         "pair_slots", "hit_pairs", "processed_samples",
                         "dropped_pairs"), 0)
    for vld, hit, t0, t1 in step_hits(centers, valid, ro, rd):
        sel = np.flatnonzero(hit)[:budget]     # hits in pair order, cut
        ts = (np.maximum(t0[sel], near)[:, None]
              + (np.arange(ns, dtype=np.float32)[None] + np.float32(0.5))
              * delta)
        out["scan_steps"] += 1
        out["live_steps"] += int(vld.any())
        if len(sel):
            out["eval_steps"] += 1
            out["pair_slots"] += min(r for r in rungs if r >= len(sel))
        out["hit_pairs"] += len(sel)
        out["dropped_pairs"] += int(hit.sum()) - len(sel)
        out["processed_samples"] += int((ts < t1[sel][:, None]).sum())
    out["sample_slots"] = out["pair_slots"] * ns
    return out


@pytest.mark.parametrize("budget", [CHUNK * 144, 16])
def test_renderer_counters_equal_numpy_recount(scene, budget):
    """Every counter the renderer returns equals the recount; at budget 16
    the budget caps `hit_pairs` and the rest are counted as dropped."""
    field, cubes = scene
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    ro, rd = (np.asarray(a) for a in rendering.camera_rays(cam))
    perm = pipeline.order_cubes(cubes, jnp.asarray(cam.origin))
    centers, valid = cubes.centers[perm], cubes.valid[perm]
    render = jax.jit(pipeline.make_ray_renderer(CFG, chunk=CHUNK,
                                                pair_budget=budget))
    _, aux = render(field, centers, valid, jnp.asarray(ro), jnp.asarray(rd))
    want = recount(np.asarray(centers), np.asarray(valid), ro, rd, budget)
    got = {k: int(aux[k]) for k in want}
    assert got == want
    assert want["live_steps"] == math.ceil(cubes.count / CHUNK)
    assert want["hit_pairs"] > 0
    assert (want["dropped_pairs"] > 0) == (budget == 16)


def fan_view(centers, valid, origin, per_step, seed=0):
    """One eye's rays aimed at points inside the valid cubes of chosen scan
    steps, `per_step` {step: rays}: a view whose steps hit from none to
    thousands of pairs."""
    rng = np.random.RandomState(seed)
    aims = []
    for step, n in per_step.items():
        ctr = centers[step * CHUNK:(step + 1) * CHUNK]
        ctr = ctr[valid[step * CHUNK:(step + 1) * CHUNK]]
        jitter = rng.uniform(-0.4, 0.4, (n, 3)) * CFG.cube_world()
        aims.append(ctr[rng.randint(0, len(ctr), n)] + jitter)
    d = np.concatenate(aims) - origin
    d = (d / np.linalg.norm(d, axis=-1, keepdims=True)).astype(np.float32)
    return np.broadcast_to(origin, d.shape).astype(np.float32), d


@pytest.mark.parametrize("budget", [1024, 2048])
def test_rungs_render_what_the_whole_budget_renders(scene, budget,
                                                    monkeypatch):
    """A view with steps of no hit, steps at each rung and (budget 1024)
    steps past the budget renders, counts and drops what the same renderer
    does with every step evaluating the whole budget."""
    field, cubes = scene
    origin = np.asarray(rays_lib.make_cameras(1, 12, 12)[0].origin)
    perm = pipeline.order_cubes(cubes, jnp.asarray(origin))
    centers, valid = cubes.centers[perm], cubes.valid[perm]
    ro, rd = fan_view(np.asarray(centers), np.asarray(valid), origin,
                      {3: 20, 9: 150, 15: 600, 21: 1300})
    steps = [(vld.any(), int(hit.sum())) for vld, hit, _, _ in
             step_hits(np.asarray(centers), np.asarray(valid), ro, rd)]
    rungs = rungs_of(budget)
    hits = [h for _, h in steps]
    assert len(rungs) == 3
    assert {min(r for r in rungs if r >= min(h, budget))
            for h in hits if h} == set(rungs)
    assert (False, 0) in steps and (True, 0) in steps
    assert (max(hits) > budget) == (budget == 1024)

    def render():
        step = jax.jit(pipeline.make_ray_renderer(CFG, chunk=CHUNK,
                                                  pair_budget=budget))
        return step(field, centers, valid, jnp.asarray(ro), jnp.asarray(rd))

    rgb, aux = render()
    monkeypatch.setattr(pipeline, "eval_rungs", lambda b: (b,))
    rgb_all, aux_all = render()
    assert int(aux["pair_slots"]) < int(aux_all["pair_slots"])
    for got, want in ((rgb, rgb_all), (aux["t_final"], aux_all["t_final"]),
                      (aux["depth"], aux_all["depth"])):
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-6
    for key in ("hit_pairs", "dropped_pairs", "active_pairs_max",
                "processed_samples"):
        assert int(aux[key]) == int(aux_all[key]), key


def _engine(scene, **kw):
    field, cubes = scene
    kw = {"ray_chunk": 64, "max_batch_views": 4,
          "adaptive_pair_budget": False, **kw}
    return RenderEngine(CFG, field, cubes, **kw)


def _record_aux(engine):
    """Wrap the engine's jitted step so each call's aux is kept."""
    seen = []
    step = engine._render

    def recording(*a):
        rgb, aux = step(*a)
        seen.append({k: int(aux[k]) for k, _ in RENDER_COUNTERS})
        return rgb, aux
    engine._render = recording
    return seen


def _render_spans(results):
    """The distinct render spans of a flush's results: a group's span is
    one interval shared by its members."""
    spans = {}
    for res in results:
        for sp in res.trace["stages"]:
            if sp["name"] == "render":
                spans[sp["dur_s"]] = sp
    return list(spans.values())


def test_engine_counters_sum_render_aux(scene):
    """Over a one-view flush and a batched three-view flush, each registry
    counter advances by exactly the renderer's aux summed over the
    calls, and the render spans carry their groups' sums."""
    engine = _engine(scene)
    seen = _record_aux(engine)
    cams = rays_lib.make_cameras(4, 12, 12)
    first = engine.render_views(cams[:1])
    n_first = len(seen)
    rest = engine.render_views(cams[1:])
    assert n_first >= 1 and len(seen) > n_first
    for key, name in RENDER_COUNTERS:
        total = sum(a[key] for a in seen)
        assert engine.metrics.counter(name).value == total, name
    for results, calls in ((first, seen[:n_first]), (rest, seen[n_first:])):
        spans = _render_spans(results)
        assert sum(sp["n_chunks"] for sp in spans) == len(calls)
        for key, _ in RENDER_COUNTERS:
            assert sum(sp[key] for sp in spans) == \
                sum(a[key] for a in calls), key


def test_engine_budget_resizes_counter(scene):
    """A resize counts in `engine_pair_budget_resizes`, which stats()
    reports under its old key."""
    engine = _engine(scene, adaptive_pair_budget=True, pair_budget=8)
    assert engine.stats()["pair_budget_resizes"] == 0
    engine.render_views(rays_lib.make_cameras(1, 12, 12))
    n = engine.metrics.counter("engine_pair_budget_resizes").value
    assert n >= 1
    assert engine.stats()["pair_budget_resizes"] == n


def test_engine_counters_without_tracing(scene):
    """Tracing off: counters still advance, no span is recorded."""
    engine = _engine(scene, trace_requests=False)
    res = engine.render_views(rays_lib.make_cameras(1, 12, 12))[0]
    assert res.trace is None
    assert engine.metrics.counter("engine_scan_steps").value > 0
    assert engine.metrics.counter("engine_samples_processed").value > 0
    assert engine.metrics.histogram("request_stage_s",
                                    stage="render").count == 0


def test_engine_counters_match_for_repeated_view(scene):
    """The same view twice advances every counter by the same amount."""
    engine = _engine(scene)
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    deltas = []
    for _ in range(2):
        before = {n: engine.metrics.counter(n).value
                  for _, n in RENDER_COUNTERS}
        engine.render_views([cam])
        deltas.append({n: engine.metrics.counter(n).value - v
                       for n, v in before.items()})
    assert deltas[0] == deltas[1]


def test_evaluated_slots_follow_rungs(scene):
    """A view that hits nothing evaluates no slot in any step; a view that
    hits evaluates one rung a hitting step (budget 128: the one rung 128),
    at least its hits, in no more steps than are live."""
    engine = _engine(scene, pair_budget=128)
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    away = rendering.look_at_camera(cam.origin, 2 * cam.origin, cam.focal,
                                    cam.h, cam.w)

    def counters_of(view):
        before = {n: engine.metrics.counter(n).value
                  for _, n in RENDER_COUNTERS}
        engine.render_views([view])
        return {n: engine.metrics.counter(n).value - v
                for n, v in before.items()}

    c = counters_of(away)
    assert c["engine_scan_steps"] > 0 and c["engine_live_steps"] > 0
    assert c["engine_hit_pairs"] == 0
    assert c["engine_eval_steps"] == c["engine_pair_slots"] == 0
    assert c["engine_sample_slots"] == 0
    c = counters_of(cam)
    assert c["engine_eval_steps"] > 0
    assert c["engine_pair_slots"] == c["engine_eval_steps"] * 128
    assert c["engine_sample_slots"] == \
        c["engine_pair_slots"] * pipeline.samples_per_segment(CFG)
    assert c["engine_pair_slots"] >= c["engine_hit_pairs"] > 0
    assert c["engine_eval_steps"] <= c["engine_live_steps"]


def test_renderer_counters_are_int32(scene):
    """Counts stay exact past float32's 2**24."""
    field, cubes = scene
    render = pipeline.make_ray_renderer(CFG, chunk=CHUNK)
    ro = jnp.zeros((16, 3), jnp.float32).at[:, 2].set(4.0)
    rd = jnp.zeros((16, 3), jnp.float32).at[:, 2].set(-1.0)
    _, aux = jax.jit(render)(field, cubes.centers, cubes.valid, ro, rd)
    for key, _ in RENDER_COUNTERS:
        assert aux[key].dtype == jnp.int32, key
