"""Per-request span tracing for the serving stack.

Cicero (PAPERS.md) grounds every rendering-pipeline change in a
stage-level latency breakdown; this module gives each served view that
breakdown. A `ViewTrace` is one request's span tree through the engine
lifecycle:

    view (submit -> deliver)
    ├── submit      enqueue under the engine lock
    ├── queue       submit -> the flush that claimed the request
    ├── group       (scene, ordering-octant) bucketing of the whole batch
    ├── ordering    per-view ordering-cache lookups for the group
    ├── compaction  micro-batch planning for the group
    ├── render      the group's ray chunks through the jitted step
    │   │             (attrs: dispatch path, chunks, pair and sample counts)
    │   ├── render.dispatch  rays to the device, the jitted call returning
    │   ├── render.device    the host waiting on the device's outputs
    │   └── render.fetch     device-to-host copies, chunks scattered to views
    └── deliver     PSNR + result construction -> future resolution

The three `render.*` children repeat once per ray chunk and tile the
`render` span. Group-level stages (group/ordering/compaction/render) are
measured once per flush group and attached to every member request's
trace — each request's tree answers "where did MY time go", and the
shared intervals are exactly the time that request spent in those stages.

Every stage is timed by `Tracer.stage`, the one way this package times
one: a `time.perf_counter()` interval recorded on the member requests'
traces, entered as a `jax.profiler.TraceAnnotation` named
`<scope>.<stage>` (`engine.render.device`, `store.encode`) over the same
interval. The annotation is a host event of the profiler, so a captured
profile (`serve --profile-dir`) shows each stage on the host thread that
ran it, on the clock of the device ops it drove; outside a capture it
costs a few hundred nanoseconds.

A `Tracer` mints traces, folds every finished trace's stage durations
into `request_stage_s{stage=...}` histograms in the shared
`MetricsRegistry` (where benchmarks and `scripts/obs_report.py` read the
stage breakdown) and counts render dispatch paths
(`render_dispatch_total{path=...}`); the finished tree itself rides on
the request's result. `enabled=False` short-circuits everything —
`start()` returns None, stages record no span and enter no annotation —
which is what the serving benchmark's self-overhead gate toggles.

Span timestamps are `time.perf_counter()` values; trees are exported with
times relative to the root so they are directly comparable across
requests.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs.registry import MetricsRegistry

# canonical stage order of one request's lifecycle (doc + report order).
# Every request passes through all of STAGES; the temporal tier's stages
# (engine.submit_delta: warp/mask on the submitting thread, composite on
# the flush thread) only appear on delta frames, so reports iterate
# REPORT_STAGES — the full lifecycle order — and skip empty stages.
STAGES = ("submit", "queue", "group", "ordering", "compaction", "render",
          "deliver")
# the children that tile a render span, once per ray chunk
RENDER_STAGES = ("render.dispatch", "render.device", "render.fetch")
REPORT_STAGES = ("warp", "mask", "submit", "queue", "group", "ordering",
                 "compaction", "render") + RENDER_STAGES + ("composite",
                                                            "deliver")


@dataclasses.dataclass
class Span:
    """One timed stage: [t0, t1] absolute perf_counter seconds + attrs."""
    name: str
    t0: float
    t1: float
    attrs: Dict = dataclasses.field(default_factory=dict)

    @property
    def dur_s(self) -> float:
        return max(self.t1 - self.t0, 0.0)


class ViewTrace:
    """The span tree of one request: a root (submit -> deliver) plus one
    child span per lifecycle stage. Built concurrently from the submitting
    thread and the flushing thread; appends are lock-protected."""

    def __init__(self, view_id: int, scene: str, t_submit: float):
        self.view_id = view_id
        self.scene = scene
        self.t_submit = t_submit
        self.t_done: Optional[float] = None
        self._lock = threading.Lock()
        self._spans: List[Span] = []

    def add(self, name: str, t0: float, t1: float, **attrs) -> Span:
        sp = Span(name, t0, t1, attrs)
        with self._lock:
            self._spans.append(sp)
        return sp

    def spans(self) -> List[Span]:
        with self._lock:
            return sorted(self._spans, key=lambda s: s.t0)

    def stage_durations(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sp in self.spans():
            out[sp.name] = out.get(sp.name, 0.0) + sp.dur_s
        return out

    def tree(self) -> Dict:
        """JSON-able span tree, times relative to submit."""
        t_end = self.t_done if self.t_done is not None else self.t_submit
        return {
            "view_id": self.view_id,
            "scene": self.scene,
            "dur_s": max(t_end - self.t_submit, 0.0),
            "stages": [
                {"name": sp.name,
                 "t0_s": max(sp.t0 - self.t_submit, 0.0),
                 "dur_s": sp.dur_s, **sp.attrs}
                for sp in self.spans()],
        }


class Stage:
    """One timed stage (`Tracer.stage`): `t0`/`t1` are perf_counter
    seconds. When the block completes, the interval is added as a span
    named `name`, with `attrs`, to every trace in `traces`; both may grow
    inside the block. A block that raises records no span."""

    def __init__(self, name: str, label: Optional[str],
                 traces: Iterable[ViewTrace], attrs: Dict):
        self.name = name
        self.traces = [t for t in traces if t is not None]
        self.attrs = attrs
        self._label = label
        self._ann = None
        self.t0 = self.t1 = 0.0

    @property
    def dur_s(self) -> float:
        return max(self.t1 - self.t0, 0.0)

    def __enter__(self) -> "Stage":
        if self._label is not None:
            self._ann = TraceAnnotation(self._label)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if exc[0] is None:
            for tr in self.traces:
                tr.add(self.name, self.t0, self.t1, **self.attrs)
        return False


class Tracer:
    """Mints ViewTraces, times stages, and folds finished traces into the
    registry. `scope` prefixes the stages' profiler annotations."""

    def __init__(self, registry: MetricsRegistry, *, enabled: bool = True,
                 scope: str = "engine"):
        self.registry = registry
        self.enabled = bool(enabled)
        self.scope = scope

    def start(self, view_id: int, scene: str,
              t_submit: Optional[float] = None) -> Optional[ViewTrace]:
        if not self.enabled:
            return None
        return ViewTrace(view_id, scene,
                         time.perf_counter() if t_submit is None
                         else t_submit)

    def stage(self, name: str, traces: Iterable[ViewTrace] = (),
              **attrs) -> Stage:
        """Context manager timing one stage on the host clock; while
        enabled it also records the span on `traces` and annotates the
        interval as `<scope>.<name>` for the profiler. Disabled, it only
        reads the clock."""
        if not self.enabled:
            return Stage(name, None, (), attrs)
        return Stage(name, f"{self.scope}.{name}", traces, attrs)

    def finish(self, trace: Optional[ViewTrace],
               t_done: Optional[float] = None):
        """Close the root span and aggregate stage durations into the
        shared registry."""
        if trace is None:
            return
        trace.t_done = time.perf_counter() if t_done is None else t_done
        for stage, dur in trace.stage_durations().items():
            self.registry.histogram("request_stage_s", stage=stage).record(
                dur)
        for sp in trace.spans():
            path = sp.attrs.get("dispatch_path")
            if path is not None:
                self.registry.counter("render_dispatch_total",
                                      path=path).inc()
