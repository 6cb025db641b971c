"""The closed loop's rules, on a scripted engine and clock: when the
window stops submitting, what a pass holds, and when warm-up stops."""
import numpy as np
import pytest

from bench import harness
from bench.tests.common import cells

CENTERS = np.zeros((1, 3), np.float32)


class Clock:
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


class NoCompiles:
    def between(self, t0, t1):
        return 0


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", c.perf_counter)
    return c


@pytest.mark.parametrize("latency,seconds,n_views", [
    (3.0, 10.0, 3),       # 0-3, 3-6, 6-9; a fourth would end at 12
    (4.0, 8.0, 2),        # a view that would end at the close is served
    (20.0, 10.0, 1),      # the first view is served whatever its length
])
def test_window_counts_every_view_it_submits(monkeypatch, clock, latency,
                                             seconds, n_views):
    def serve(engine, pose):
        clock.t += latency
        return harness.View(pose, clock.t, None, latency, latency, False)

    monkeypatch.setattr(harness, "serve", serve)
    monkeypatch.setattr(harness, "read_counters", lambda engine: {})
    _, _, _, mix, _ = harness.load_cell(cells()[0])
    mix = dict(mix, pool={"seed": 7, "views": 1})        # passes of one
    win = harness.serve_window(None, mix, CENTERS, 2**31 + 3, seconds, None,
                               NoCompiles())
    assert len(win.views) == n_views
    assert win.t_end == pytest.approx(n_views * latency)
    e2e = harness.end_to_end(win, 1, 1.0)
    assert e2e["rays_per_s"] == pytest.approx(
        n_views * win.views[0].pose.n_rays / (n_views * latency))
    assert e2e["view_ms_p95"] == pytest.approx(latency * 1e3)


@pytest.mark.parametrize("latency,seconds,n_passes", [
    (1.0, 10.0, 3),       # passes of 3 s: 0-3, 3-6, 6-9; a fourth ends at 12
    (4.0, 10.0, 1),       # the first pass is served whole, past the close
])
def test_window_serves_whole_passes_of_the_pool(monkeypatch, clock, latency,
                                                seconds, n_passes):
    def serve(engine, pose):
        clock.t += latency
        return harness.View(pose, clock.t, None, latency, latency, False)

    monkeypatch.setattr(harness, "serve", serve)
    monkeypatch.setattr(harness, "read_counters", lambda engine: {})
    _, _, _, mix, _ = harness.load_cell(cells()[0])
    mix = dict(mix, pool={"seed": 7, "views": 3})
    win = harness.serve_window(None, mix, CENTERS, 2**31 + 3, seconds, None,
                               NoCompiles())
    assert len(win.views) == 3 * n_passes
    assert win.t_end == pytest.approx(3 * n_passes * latency)


def test_every_seed_gets_the_pool_in_its_own_order():
    from bench import traffic
    _, _, _, mix, _ = harness.load_cell(cells()[0])
    mix = dict(mix, pool={"seed": 7, "views": 5})

    def key(p):
        return tuple(p.origin.tolist())
    orders = []
    for seed in (2**31 + 3, 11):
        it = traffic.passes(mix, CENTERS, seed)
        first, second = next(it), next(it)
        assert [key(p) for p in first] == [key(p) for p in second]
        orders.append([key(p) for p in first])
    assert sorted(orders[0]) == sorted(orders[1])
    assert orders[0] != orders[1]
    assert len(set(orders[0])) == 5


class ScriptedEngine:
    """Each served view leaves the budget and the fill of the script."""

    def __init__(self, budget, script):
        self.budget, self.fill, self.script = budget, 0.0, list(script)

    def stats(self):
        return {"pair_budget": self.budget, "pair_occupancy_last": self.fill}


@pytest.mark.parametrize("script,n_views", [
    # fovea: the first view fills over a quarter of the unchanged budget
    ([(8192, 0.4)], 1),
    # tiny widths: the budget doubles, then holds at 92% filled
    ([(16384, 1.0), (16384, 0.925)], 2),
    # periphery: three low views shrink it, three more leave it put
    ([(8192, 0.006), (8192, 0.006), (256, 0.006), (256, 0.21),
      (256, 0.21), (256, 0.21)], 6),
])
def test_warm_up_stops_once_the_budget_settles(monkeypatch, clock, script,
                                               n_views):
    engine = ScriptedEngine(8192, script + [(1, 0.0)] * 8)

    def serve(eng, pose):
        clock.t += 1.0
        eng.budget, eng.fill = eng.script.pop(0)
        return harness.View(pose, clock.t, None, 1.0, 1.0, False)

    monkeypatch.setattr(harness, "serve", serve)
    _, _, _, mix, _ = harness.load_cell(cells()[0])
    n = harness.warm_up(engine, mix, CENTERS, NoCompiles(), lambda m: None)
    assert n == n_views
