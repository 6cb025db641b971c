"""Observability stack: metrics registry semantics, exposition formats,
request span tracing, and the engine/store/fine-tune integration —
bit-compatible stats(), complete span trees, and exact drop/timeout
accounting under concurrent multi-scene submits."""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs.rtnerf import NeRFConfig
from repro.core import field as field_lib
from repro.core import occupancy as occ_lib
from repro.core import tensorf
from repro.data import rays as rays_lib
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       MetricsServer, StatsReporter, Tracer, snapshot_json,
                       to_prometheus)
from repro.obs.tracing import RENDER_STAGES, STAGES
from repro.serving import RenderEngine, SceneStore

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


# -- registry primitives ---------------------------------------------------


def test_counter_monotone():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_gauge_last_write_wins():
    g = MetricsRegistry().gauge("depth")
    g.set(4)
    g.inc()
    g.set(2)
    assert g.value == 2.0


def test_histogram_window_bound_and_alltime_max():
    """The ring keeps only `maxlen` observations for percentiles, but
    count/sum/max are all-time — the SceneRecord.swap_latencies contract
    (bounded memory, worst-case survives the window rolling over)."""
    h = MetricsRegistry().histogram("lat", maxlen=8)
    h.record(100.0)                       # the all-time max, soon evicted
    for v in range(1, 21):
        h.record(float(v))
    assert len(h.window()) == 8
    assert h.count == 21
    assert h.max == 100.0                 # evicted from the window, kept
    assert h.window().max() == 20.0       # window knows only recent values
    assert h.last == 20.0
    assert h.sum == pytest.approx(100.0 + sum(range(1, 21)))
    # percentiles cover the resident window exactly
    assert h.percentile(50) == pytest.approx(
        float(np.percentile(np.arange(13, 21, dtype=float), 50)))


def test_registry_labels_and_handle_caching():
    reg = MetricsRegistry()
    a = reg.counter("views", scene="lego")
    b = reg.counter("views", scene="chair")
    assert a is not b
    a.inc(3)
    assert reg.counter("views", scene="lego") is a      # cached handle
    assert reg.counter("views", scene="lego").value == 3
    assert b.value == 0


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_snapshot_schema():
    reg = MetricsRegistry()
    reg.counter("c", scene="lego").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", maxlen=4).extend([1.0, 2.0, 3.0])
    snap = reg.snapshot()
    assert snap["counters"]["c{scene=lego}"]["value"] == 2
    assert snap["gauges"]["g"]["value"] == 1.5
    h = snap["histograms"]["h"]
    assert h["count"] == 3 and h["window_len"] == 3 and h["maxlen"] == 4
    assert h["p50"] == 2.0 and h["max"] == 3.0 and h["last"] == 3.0
    # the envelope is JSON-able as-is
    json.dumps(snapshot_json(reg, extra={"fps": 1.0}))


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("v", maxlen=128)

    def work():
        for i in range(500):
            c.inc()
            h.record(float(i))

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 4000
    assert h.count == 4000
    assert len(h.window()) == 128


# -- exposition ------------------------------------------------------------


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("views_total", scene="lego").inc(5)
    reg.gauge("queue_depth").set(2)
    reg.histogram("latency_s").extend([0.1, 0.2, 0.3])
    text = to_prometheus(reg)
    assert "# TYPE views_total counter" in text
    assert 'views_total{scene="lego"} 5' in text
    assert "# TYPE queue_depth gauge" in text
    assert "# TYPE latency_s summary" in text
    assert 'latency_s{quantile="0.5"} ' in text
    assert "latency_s_count 3" in text
    assert text.endswith("\n")


def test_metrics_server_endpoints():
    reg = MetricsRegistry()
    reg.counter("hits").inc(7)
    with MetricsServer(reg, port=0,
                       extra=lambda: {"fps": 12.5}) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics.json").read()
        snap = json.loads(body)
        assert snap["schema"] == "repro.obs/v1"
        assert snap["metrics"]["counters"]["hits"]["value"] == 7
        assert snap["stats"]["fps"] == 12.5
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "hits 7" in text
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")


def test_stats_reporter_emits_and_stops(capsys):
    rep = StatsReporter(lambda: "tick", interval_s=0.02)
    time.sleep(0.1)
    rep.close()
    out = capsys.readouterr().out
    assert "tick" in out


# -- tracing ---------------------------------------------------------------


def test_tracer_span_tree_and_stage_histograms():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    t = tr.start(1, "lego", t_submit=100.0)
    t.add("submit", 100.0, 100.01)
    t.add("queue", 100.0, 100.2)
    t.add("render", 100.2, 100.7, dispatch_path="fused", n_chunks=3)
    t.add("deliver", 100.7, 100.71)
    tr.finish(t, t_done=100.71)
    tree = t.tree()
    assert tree["view_id"] == 1 and tree["scene"] == "lego"
    assert tree["dur_s"] == pytest.approx(0.71)
    names = [s["name"] for s in tree["stages"]]
    assert names == ["submit", "queue", "render", "deliver"]  # t0 order
    render = tree["stages"][2]
    assert render["dispatch_path"] == "fused" and render["n_chunks"] == 3
    assert render["t0_s"] == pytest.approx(0.2)
    # stage durations folded into the shared registry
    assert reg.histogram("request_stage_s", stage="render").count == 1
    assert reg.histogram("request_stage_s", stage="render").last == \
        pytest.approx(0.5)
    assert reg.counter("render_dispatch_total", path="fused").value == 1
    assert t.t_done == 100.71


def test_tracer_disabled_noops():
    reg = MetricsRegistry()
    tr = Tracer(reg, enabled=False)
    assert tr.start(1, "lego") is None
    tr.finish(None)                       # must not raise
    other = Tracer(reg).start(2, "lego", t_submit=0.0)
    with tr.stage("render", [other]) as st:
        pass
    assert st.dur_s >= 0.0                # the clock still reads
    assert other.spans() == []            # ... but nothing is recorded
    assert reg.metrics() == []


def test_tracer_stage_records_on_traces():
    """A stage adds one span, with attrs set inside the block, to every
    trace it holds, including one appended inside; a block that raises
    records none."""
    tr = Tracer(MetricsRegistry())
    a = tr.start(1, "lego", t_submit=0.0)
    b = tr.start(2, "lego", t_submit=0.0)
    with tr.stage("render", [a, None], n_chunks=2) as st:
        st.attrs["dropped_pairs"] = 0
        st.traces.append(b)
    assert st.t1 >= st.t0 > 0.0
    for t in (a, b):
        (sp,) = t.spans()
        assert (sp.name, sp.t0, sp.t1) == ("render", st.t0, st.t1)
        assert sp.attrs == {"n_chunks": 2, "dropped_pairs": 0}
    with pytest.raises(RuntimeError):
        with tr.stage("deliver", [a]):
            raise RuntimeError("render failed")
    assert [sp.name for sp in a.spans()] == ["render"]


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`, keeping the labels
    entered, in order."""

    def __init__(self):
        self.labels = []
        self.open = 0
        log = self

        class Ann:
            def __init__(self, label):
                self.label = label

            def __enter__(self):
                log.labels.append(self.label)
                log.open += 1

            def __exit__(self, *exc):
                log.open -= 1
        self.cls = Ann


@pytest.fixture
def annotations(monkeypatch):
    from repro.obs import tracing
    ann = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", ann.cls)
    return ann


# -- engine integration ----------------------------------------------------


def test_request_span_tree_complete():
    """Acceptance: one rendered request produces a complete span tree —
    submit through deliver, every group stage present, and the render span
    tagged with the field's dispatch path."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=64,
                          max_batch_views=2, encode=True)
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    fut = engine.submit(cam)
    engine.flush()
    res = fut.result()
    assert res.trace is not None
    names = [s["name"] for s in res.trace["stages"]]
    for stage in STAGES:
        assert stage in names, f"stage '{stage}' missing from {names}"
    render = next(s for s in res.trace["stages"] if s["name"] == "render")
    assert render["dispatch_path"] == engine.store.snapshot(
        engine.default_scene).field.dispatch_path()
    assert render["dur_s"] > 0
    assert res.trace["dur_s"] >= render["dur_s"]
    # the result carries its own tree; stage histograms one observation each
    assert res.trace["view_id"] == res.view_id
    for stage in STAGES:
        assert engine.metrics.histogram("request_stage_s",
                                        stage=stage).count >= 1
    br = engine.stage_breakdown()
    assert set(br) == set(STAGES) | set(RENDER_STAGES)
    assert br["render"]["count"] == 1


def test_engine_stats_registry_backed_and_tracing_toggle():
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=64,
                          max_batch_views=2)
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    engine.render_views([cam])
    s = engine.stats()
    assert s["views_served"] == 1
    assert s["latency_p99_s"] >= s["latency_p50_s"] > 0
    # the same numbers are visible through the registry snapshot
    snap = engine.metrics.snapshot()
    assert snap["counters"]["engine_views_served"]["value"] == 1
    assert snap["histograms"]["engine_latency_s"]["count"] == 1
    # tracing off: requests render fine, no new traces minted
    engine.set_tracing(False)
    renders = engine.metrics.histogram("request_stage_s", stage="render")
    n_before = renders.count
    r = engine.render_views([cam])[0]
    assert r.trace is None and not r.timed_out and r.img is not None
    assert renders.count == n_before
    assert engine.stats()["views_served"] == 2


def test_drop_timeout_accounting_concurrent_multiscene():
    """stats()['timeouts'] and per-future timed_out flags must agree
    exactly under concurrent submits across scenes — every future resolves
    exactly once as either served or timed out, and the registry counters
    sum to the observed outcomes."""
    field_a, cubes_a = _field_and_cubes(seed=0)
    field_b, cubes_b = _field_and_cubes(seed=1)
    engine = RenderEngine(CFG, field_a, cubes_a, scene_name="a",
                          ray_chunk=64, max_batch_views=4)
    engine.register_scene("b", field_b, cubes_b)
    cams = rays_lib.make_cameras(4, 12, 12)
    engine.render_views(cams[:1], scene="a")      # compile outside timing
    engine.render_views(cams[:1], scene="b")
    base_views = engine.stats()["views_served"]

    futs, lock = [], threading.Lock()

    def submit_stream(scene, deadline):
        mine = [engine.submit(cam, scene=scene, deadline_s=deadline)
                for cam in cams]
        with lock:
            futs.extend(mine)

    threads = [
        threading.Thread(target=submit_stream, args=("a", None)),
        threading.Thread(target=submit_stream, args=("b", None)),
        # deadline already expired at flush time: these MUST time out
        threading.Thread(target=submit_stream, args=("a", 1e-9)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.flush()
    results = [f.result() for f in futs]

    n_timed_out = sum(r.timed_out for r in results)
    n_served = sum(not r.timed_out for r in results)
    assert len(results) == 12
    assert n_timed_out == 4                      # exactly the stale stream
    s = engine.stats()
    assert s["timeouts"] == n_timed_out
    assert s["views_served"] - base_views == n_served
    assert int(engine.metrics.counter("engine_timeouts").value) == \
        n_timed_out
    # timed-out traces still close, flagged, without a render span
    to_traces = [r.trace for r in results if r.timed_out]
    assert all(t is not None for t in to_traces)
    for t in to_traces:
        deliver = [st for st in t["stages"] if st["name"] == "deliver"]
        assert deliver and deliver[0]["timed_out"] is True
        assert not any(st["name"] == "render" for st in t["stages"])
    # dropped-pair accounting: counter matches the sum over render spans
    dropped_spans = sum(
        st.get("dropped_pairs", 0)
        for r in results if r.trace is not None
        for st in r.trace["stages"] if st["name"] == "render")
    assert int(engine.metrics.counter("engine_dropped_pairs").value) == \
        dropped_spans


def test_store_and_engine_share_registry():
    """One registry per store: the engine and the store's scene records
    (and any attached fine-tuner — tests/test_finetune.py) record into the
    same registry, so exposition reads one coherent snapshot."""
    field, cubes = _field_and_cubes()
    reg = MetricsRegistry()
    store = SceneStore(CFG, registry=reg)
    store.register("lego", field, cubes)
    engine = RenderEngine(CFG, store=store, ray_chunk=64, max_batch_views=2)
    assert engine.metrics is reg and store.metrics is reg
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    engine.render_views([cam], scene="lego")
    snap = reg.snapshot()
    assert snap["counters"]["scene_views_served{scene=lego}"]["value"] == 1
    assert snap["counters"]["engine_views_served"]["value"] == 1
    # scene stats() keys stay registry-sourced and bit-compatible
    sc = engine.stats(scene="lego")
    assert sc["views_served"] == 1
    assert sc["latency_p50_s"] == pytest.approx(
        snap["histograms"]["scene_latency_s{scene=lego}"]["p50"])
    assert sc["latency_p50_s"] > 0


def test_engine_registry_conflict_rejected():
    field, cubes = _field_and_cubes()
    store = SceneStore(CFG)
    store.register("lego", field, cubes)
    with pytest.raises(ValueError):
        RenderEngine(CFG, store=store, registry=MetricsRegistry(),
                     ray_chunk=64)


def test_engine_stages_annotated_for_the_profiler(annotations):
    """Traced, every engine stage enters an `engine.<stage>` annotation
    (the render children once per chunk) and leaves it; untraced, none
    is entered while the work counters still advance."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=64,
                          max_batch_views=2)
    cam = rays_lib.make_cameras(1, 12, 12)[0]
    annotations.labels.clear()
    res = engine.render_views([cam])[0]
    seen = set(annotations.labels)
    for stage in ("submit", "group", "ordering", "compaction", "render",
                  "deliver") + RENDER_STAGES:
        assert f"engine.{stage}" in seen, stage
    n_chunks = next(s for s in res.trace["stages"]
                    if s["name"] == "render")["n_chunks"]
    assert annotations.labels.count("engine.render.device") == n_chunks
    assert annotations.open == 0
    steps = engine.metrics.counter("engine_scan_steps").value
    engine.set_tracing(False)
    annotations.labels.clear()
    r = engine.render_views([cam])[0]
    assert r.trace is None and annotations.labels == []
    assert engine.metrics.counter("engine_scan_steps").value == 2 * steps


def test_render_children_tile_render_span():
    """render.dispatch, render.device and render.fetch lie inside their
    render span, without overlap, once per chunk (fetch once more for the
    scatter)."""
    field, cubes = _field_and_cubes()
    engine = RenderEngine(CFG, field, cubes, ray_chunk=64,
                          max_batch_views=2)
    cams = rays_lib.make_cameras(2, 12, 12)
    for res in engine.render_views(cams):
        st = res.trace["stages"]
        render = next(s for s in st if s["name"] == "render")
        lo, hi = render["t0_s"], render["t0_s"] + render["dur_s"]
        kids = sorted((s for s in st if s["name"] in RENDER_STAGES),
                      key=lambda s: s["t0_s"])
        counts = {n: sum(s["name"] == n for s in kids) for n in RENDER_STAGES}
        assert counts == {"render.dispatch": render["n_chunks"],
                          "render.device": render["n_chunks"],
                          "render.fetch": render["n_chunks"] + 1}
        end = lo
        for s in kids:
            assert s["t0_s"] >= end - 1e-9
            end = s["t0_s"] + s["dur_s"]
        assert end <= hi + 1e-9
        assert sum(s["dur_s"] for s in kids) <= render["dur_s"] + 1e-9


@pytest.mark.parametrize("encode", [True, False])
def test_store_prepare_stages_timed(annotations, encode):
    """Registration times its set-up stages into store_prepare_s and
    annotates them as store.<stage>; occupancy only when it is built."""
    field, cubes = _field_and_cubes()
    store = SceneStore(CFG, encode=encode)
    store.register("given", field, cubes)
    store.register("built", field)
    first = "encode" if encode else "decode"
    h = {st: store.metrics.histogram("store_prepare_s", stage=st)
         for st in (first, "place", "occupancy")}
    assert (h[first].count, h["place"].count, h["occupancy"].count) == \
        (2, 2, 1)
    assert all(x.sum > 0 for x in h.values())
    assert annotations.labels == [f"store.{first}", "store.place"] * 2 + \
        ["store.occupancy"]
