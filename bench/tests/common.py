"""Helpers shared by the benchmark's CPU tests."""
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


def rehearse(cell, seed=5, seconds=10.0, trace=False):
    """(exit code, result dict or None) of one CPU rehearsal run."""
    from bench import harness
    buf = io.StringIO()
    rc = harness.run(cell, seed, seconds, trace, rehearse=True, out=buf)
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
