"""A field architecture enters the benchmark as files: its module under
`bench/archs/`, a configuration that names it, a limits file and the
cell's entry in BENCHMARK.json. The harness and the control call only the
module; the program's counters reach the per-layer readers."""
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench.tests.common import ROOT, rehearse
from bench.tests.test_bench_shapes import arch, widths

INTERFACE = ("make_weights", "build", "reference_field", "ops_per_sample",
             "bytes_per_view", "rehearsal_widths")


def test_every_configuration_names_an_architecture():
    from bench import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        name = conf["arch"]
        assert os.path.exists(os.path.join(ROOT, "bench", "archs",
                                           f"{name}.py")), name
        mod = harness.load_arch(name)
        for fn in INTERFACE:
            assert callable(getattr(mod, fn)), (name, fn)
        assert mod.SCOPES and all(isinstance(s, str) for s in mod.SCOPES)
        assert "composite" in mod.ops_per_sample(conf["field"])


# SHA-256 over the sorted leaves' names and bytes of the weights that
# `bench/inputs.make_weights` made at rehearsal widths before the move
WEIGHTS_SHA = {
    3: "3f4588304da98f9e12fe64101cd551fa478425cb4d14119ccd57fb2350ce2b1b",
    2**31 + 5:
        "1795520577d03df9ce8dd98ae6a668e56a6da288c867e9cf8014ab47373de897",
}


@pytest.mark.parametrize("seed", sorted(WEIGHTS_SHA))
def test_tensorf_vm_weights_are_those_of_before(seed):
    p = arch().make_weights(widths(), seed)
    h = hashlib.sha256()
    for k in sorted(p):
        h.update(k.encode())
        h.update(np.asarray(p[k]).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA[seed]


def test_tensorf_vm_operations_at_published_widths():
    ops = arch().ops_per_sample(widths(tiny=False)["field"])
    assert sum(ops.values()) == 82622


@pytest.fixture
def copy_root(tmp_path, monkeypatch):
    """A checkout of the benchmark with a second architecture added as
    files only: `tensorf_vm_copy` (a renamed copy of `tensorf_vm`), its
    configuration, the limits of its cell and the cell's entry."""
    from bench import harness
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    b = tmp_path / "bench"
    shutil.copy(b / "archs" / "tensorf_vm.py", b / "archs" /
                "tensorf_vm_copy.py")
    conf = json.loads((b / "configs" / "tensorf-vm-hybrid.json").read_text())
    conf.update(name="tensorf-vm-copy", arch="tensorf_vm_copy")
    (b / "configs" / "tensorf-vm-copy.json").write_text(json.dumps(conf))
    shutil.copy(b / "limits" / "hybrid-fovea.json",
                b / "limits" / "copy-fovea.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tensorf-vm-copy",
        "source": "https://arxiv.org/abs/2203.09517",
        "file": "bench/configs/tensorf-vm-copy.json", "reduced": [],
        "why": "a second architecture, added as files"})
    bench["workloads"].append({
        "name": "copy-fovea", "config": "tensorf-vm-copy", "traffic": "fovea",
        "chips": 1, "why": "the second architecture's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    jax.clear_caches()
    yield b / "archs" / "tensorf_vm_copy.py"
    jax.clear_caches()


def test_architecture_added_as_files_runs_correct(copy_root):
    from bench import harness
    assert harness.load_cell("copy-fovea")[4].__name__.endswith(
        "tensorf_vm_copy")
    rc, res = rehearse("copy-fovea", seed=2**31 + 41)
    assert rc == 0
    assert res["correct"] is True, res["check"]
    assert res["check"]["rmse"]["value"] < 1e-5


def test_architecture_added_as_files_fails_on_a_wrong_reference(copy_root):
    """One weight of the copy's reference field changed: the served views
    no longer match it."""
    src = copy_root.read_text()
    old = 'matmul(h, params["mlp_w3"]) + params["mlp_b3"]'
    assert src.count(old) == 1
    copy_root.write_text(src.replace(
        old, 'matmul(h, params["mlp_w3"]) + params["mlp_b3"].at[0].add(0.01)'))
    rc, res = rehearse("copy-fovea", seed=2**31 + 41)
    assert rc == 0
    assert res["correct"] is False, res["check"]


def test_counter_readers_read_the_rehearsal_counts(monkeypatch):
    """A traced rehearsal's readers get the change of the program's
    counters over the window: one view, one ray chunk of 4096 rays, so the
    scan's steps are the padded cube list over the cube chunk, and the
    steps that evaluate are those a CPU recount finds hits in."""
    from bench import geometry, harness
    seen = {}
    orig = harness.per_layer

    def per_layer(bench, cell, arch_mod, win, *a):
        seen["win"], seen["hits"] = win, a[-1]
        seen["out"] = orig(bench, cell, arch_mod, win, *a)
        return seen["out"]
    monkeypatch.setattr(harness, "per_layer", per_layer)
    rc, res = rehearse("hybrid-fovea", seed=2**31 + 43, trace=True)
    assert rc == 0 and res["correct"] is True
    c, w = seen["win"].counters, widths()["field"]
    assert len(seen["win"].views) == 1
    assert c["engine_scan_steps"] == -(-w["max_cubes"] // 8)
    assert c["engine_eval_steps"] == len(np.unique(seen["hits"].step))
    assert c["engine_views_served"] == 1
    out = seen["out"]
    assert out["renderer.eval_step_share"]["value"] == pytest.approx(
        100.0 * c["engine_eval_steps"] / c["engine_scan_steps"])
    assert out["field_eval.sample_fill"]["value"] == pytest.approx(
        100.0 * c["engine_samples_processed"] / c["engine_sample_slots"])
    assert 0 < out["field_eval.sample_fill"]["value"] <= 100
    assert geometry.samples_per_segment(w) * c["engine_pair_slots"] == \
        c["engine_sample_slots"]
