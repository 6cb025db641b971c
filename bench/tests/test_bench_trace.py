"""Trace reduction: busy and idle time, device time per named scope and
idle gaps by host annotation, on hand-made events and on a small trace
recorded on a TPU v5e (bench/testdata)."""
import os

import pytest

from bench import trace
from bench.tests.common import ROOT
from bench.tests.test_bench_shapes import arch

FIELD_SCOPES = arch().SCOPES
NAMED = trace.SCOPES + FIELD_SCOPES


def op(name, scope, s, e):
    return trace.Op(name, scope, s, e)


def test_merge_and_clip():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert trace.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_scope_of_takes_the_innermost():
    assert trace.scope_of("jit(render)/while/body/rtnerf.field_eval/"
                          "fused.decode.m0/gather", NAMED) == "fused.decode"
    assert trace.scope_of("jit(render)/rtnerf.compact/sort", NAMED) == \
        "rtnerf.compact"
    assert trace.scope_of("jit(camera_rays)/mul", NAMED) is None
    # without the architecture's scopes, its ops count in field_eval only
    assert trace.scope_of("jit(render)/while/body/rtnerf.field_eval/"
                          "fused.decode.m0/gather",
                          trace.SCOPES) == "rtnerf.field_eval"


def test_reduce_hand_made():
    # window 0..10 s from the harness's annotations; two devices
    host = [("bench.submit", 0.0, 1.0), ("bench.result", 1.0, 10.0),
            ("$engine.py:692 flush", 1.0, 9.5), ("np.asarray", 8.0, 9.5)]
    dev0 = [op("fusion.1", "rtnerf.intersect", 2.0, 3.0),
            op("fusion.2", "fused.decode", 3.0, 5.0),
            op("fusion.2", "fused.decode", 5.5, 6.0),
            op("sort.1", "rtnerf.compact", 9.8, 11.0)]    # cut at 10
    dev1 = [op("fusion.1", "rtnerf.intersect", 0.0, 4.0)]
    s = trace.reduce([dev0, dev1], host, FIELD_SCOPES)
    assert s.window_s == pytest.approx(10.0)
    # device 0 busy 1 + 2 + 0.5 + 0.2, device 1 busy 4: mean 3.85
    assert s.busy_s == pytest.approx(3.85)
    assert s.scope_s["fused.decode"] == pytest.approx(2.5)
    assert s.scope_s["rtnerf.field_eval"] == pytest.approx(2.5)
    assert s.scope_s["rtnerf.intersect"] == pytest.approx(5.0)
    assert s.scope_s["rtnerf.compact"] == pytest.approx(0.2)
    assert s.top_ops[0] == ("rtnerf.intersect:fusion.1", pytest.approx(5.0))
    # device 0's gaps: 0-2 (submit covers 0.5 at mid 1.0: innermost of
    # bench.submit / bench.result at the boundary), 6-9.8 (mid 7.9:
    # flush), 5-5.5 (flush)
    gaps = dict((round(d, 6), n) for n, d in s.idle_gaps)
    assert gaps[3.8] == "$engine.py:692 flush"
    assert gaps[0.5] == "$engine.py:692 flush"
    assert set(gaps) == {2.0, 3.8, 0.5}


def test_a_control_op_counts_as_busy_not_as_an_op():
    host = [("bench.result", 0.0, 10.0)]
    dev = [op("while.7", None, 1.0, 9.0),
           op("fusion.3", "rtnerf.field_eval", 2.0, 4.0),
           op("fusion.4", "rtnerf.scatter", 4.0, 5.0)]
    s = trace.reduce([dev], host, ())
    assert s.busy_s == pytest.approx(8.0)
    assert [n for n, _ in s.top_ops] == ["rtnerf.field_eval:fusion.3",
                                         "rtnerf.scatter:fusion.4"]


def test_reduce_needs_device_ops_and_annotations():
    assert trace.reduce([], [("bench.submit", 0, 1)],
                        FIELD_SCOPES) is None
    assert trace.reduce([[op("f", None, 0, 1)]], [("other", 0, 1)],
                        FIELD_SCOPES) is None


@pytest.fixture(scope="module")
def tiny_trace(tmp_path_factory):
    """A trace of one view served at the program's tiny widths on one TPU
    v5e (recorded by the harness's `--trace 1` path)."""
    import gzip
    import shutil
    src = os.path.join(ROOT, "bench", "testdata",
                       "tiny_hybrid_fovea.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(dst)


def test_recorded_trace(tiny_trace):
    """Expected numbers checked by hand: the window and busy time against
    a 1-us timeline of the raw events (0.535927 s, 0.494853 s); the
    scopes of fusion.493 (fused.sample.m2) and fusion.446 (field_eval,
    outside fused.*) read from the op paths in the file's bytes."""
    s = trace.reduce(*trace.read(tiny_trace, FIELD_SCOPES), FIELD_SCOPES)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.535927065, abs=1e-9)
    assert s.busy_s == pytest.approx(0.494853, abs=5e-6)
    expect = {"rtnerf.field_eval": 0.443336, "fused.sample": 0.366188,
              "fused.decode": 0.034343, "fused.accumulate": 0.005455,
              "rtnerf.compact": 0.020524, "rtnerf.scatter": 0.016674,
              "rtnerf.composite": 0.001823, "rtnerf.intersect": 0.000308}
    for k, v in expect.items():
        assert s.scope_s[k] == pytest.approx(v, abs=2e-6), k
    names = dict(s.top_ops)
    assert "fused.sample:fusion.493" in names
    assert "rtnerf.field_eval:fusion.446" in names
    # every gap is named by a host event of the thread that drove the run
    assert s.idle_gaps and all(n != "-" for n, _ in s.idle_gaps[:10])
    assert s.breakdown()["device_ops"][0][1] > 0


def test_recorded_trace_reduces_as_before(tiny_trace):
    """Every number of the reduction (window, busy time, time per scope,
    every op and every idle gap) is the one the reduction gave before the
    architecture named its own scopes: the SHA-256 of the summary as JSON,
    taken with the `fused.*` scopes written into `bench/trace.py`."""
    import hashlib
    import json
    s = trace.reduce(*trace.read(tiny_trace, FIELD_SCOPES), FIELD_SCOPES)
    blob = json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                       "scope_s": sorted(s.scope_s.items()),
                       "top_ops": s.top_ops, "idle_gaps": s.idle_gaps})
    assert (len(s.top_ops), len(s.idle_gaps)) == (732, 137)
    assert hashlib.sha256(blob.encode()).hexdigest() == \
        "34d76061fb22a4c9421cf04969622497a04686673426f872123d998b49fda26a"
