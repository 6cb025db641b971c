"""Every cell's harness end to end on the CPU at the program's tiny widths,
and the refusal to report anything without a chip."""
import os
import subprocess
import sys

import pytest

from bench.tests.common import ROOT, cells, rehearse


@pytest.mark.parametrize("cell", cells())
def test_cell_rehearses_and_matches_reference(cell):
    rc, res = rehearse(cell, seed=2**31 + 17)
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["rehearsal"] is True
    assert res["metrics"] == {}                  # no device metric off-chip
    assert "busy_s" not in res["device"]
    # the engine and the plain reference agree to float32 rounding on the
    # CPU, for the hybrid-encoded and the dense field alike
    assert res["check"]["rmse"]["value"] < 1e-5
    assert list(res)[-1] == "check"


def test_traced_rehearsal_reports_no_device_metric():
    rc, res = rehearse(cells()[0], seed=23, trace=True)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"] == {} and "breakdown" not in res


def test_harness_refuses_cpu():
    import io

    from bench import harness
    buf = io.StringIO()
    rc = harness.run(cells()[0], 1, 1.0, False, rehearse=False, out=buf)
    assert rc != 0 and buf.getvalue() == ""


def test_command_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         cells()[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
