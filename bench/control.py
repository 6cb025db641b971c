"""Readings that the comparison's limits are set from, for one cell.

For each seed: the seed's field is published into one engine (set up and
warmed up once, as a run does), the first views of the window's passes
for that seed are served, and each served view is compared with the
float32 reference (what a sound run reads). On the first
`--control-seeds` seeds the control is read too, and has to come out not
correct: the configuration's `control` entry, the program itself at a
lower `matmul_precision` (its views are served again after every seed's)
or the reference in a lower `dtype` put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 101 102 103 \
        [--views 1] [--control-seeds 3] [--rehearse]

Prints one JSON line per seed, then a summary line: the largest program
reading and the smallest control reading of each number.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(workload, seeds, views=1, control_seeds=3, rehearse=False,
             log=None, control=None):
    """[(seed, program readings, control readings or None)]; `control`
    stands in for the configuration's entry."""
    import jax.numpy as jnp

    from bench import compare, device, geometry, harness, inputs, reference
    from bench import traffic
    from repro.core import occupancy as occ_lib

    log = log or (lambda m: print(f"[control] {m}", file=sys.stderr,
                                  flush=True))
    _, cell, conf, mix, arch = harness.load_cell(workload)
    control = control or conf["control"]
    w = dict(conf["field"])
    if rehearse:
        w.update(arch.rehearsal_widths())
        conf = dict(conf, field=w)
    harness.enable_compile_cache()
    harness.set_precision(conf)
    devs = device.devices(cell["chips"], rehearse)
    occ = inputs.occupancy(conf)
    centers = inputs.cube_centers(conf, occ)
    scene = conf["scene"]["name"]
    engine = cubes = None
    out, again = [], []
    for k, seed in enumerate(seeds):
        params = arch.make_weights(conf, seed)
        cfg, field = arch.build(conf, w, params)
        if engine is None:
            cubes = occ_lib.extract_cubes(jnp.asarray(occ), cfg)
            engine = harness.make_engine(cfg, field, cubes, conf, scene,
                                         harness.make_mesh(devs))
            harness.warm_up(engine, mix, centers, harness.CompileLog(),
                            log)
        else:
            engine.swap_field(field, cubes)
        poses = (p for batch in traffic.passes(mix, centers, seed)
                 for p in batch)
        served = [harness.serve(engine, next(poses)) for _ in range(views)]
        imgs, refs, ctrl = [], [], []
        for v in served:
            ro, rd = v.pose.rays()
            h = geometry.hits(w, centers, ro, rd, engine.cube_chunk)
            imgs.append(v.img)
            refs.append(reference.render(arch.reference_field, params, w, h,
                                         ro, rd))
            if k < control_seeds and "dtype" in control:
                ctrl.append(reference.render(arch.reference_field, params, w,
                                             h, ro, rd, **control)[0])
        if k < control_seeds and "matmul_precision" in control:
            again.append((k, field, [v.pose for v in served], refs))
        prog = compare.readings(imgs, refs)
        con = compare.readings(ctrl, refs) if ctrl else None
        log(f"seed {seed}: program {prog}, control {con}, "
            f"latencies {[round(v.latency_s, 3) for v in served]}")
        out.append([seed, prog, con])
    if again:
        harness.set_precision(control)
        for k, field, served, refs in again:
            engine.swap_field(field, cubes)
            out[k][2] = compare.readings(
                [harness.serve(engine, p).img for p in served], refs)
            log(f"seed {out[k][0]}: control {out[k][2]} (the program at "
                f"{control})")
    engine.close()
    return [tuple(r) for r in out]


def main(argv=None) -> int:
    import argparse
    import json

    from bench import compare

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--views", type=int, default=1)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    rows = readings(a.workload, a.seeds, a.views, a.control_seeds,
                    a.rehearse)
    for seed, prog, con in rows:
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "program": prog, "control": con}), flush=True)
    summary = {"workload": a.workload, "seeds": len(rows)}
    for k in compare.NUMBERS:
        summary[f"program_max_{k}"] = max(p[k] for _, p, _ in rows)
        cons = [c[k] for _, _, c in rows if c is not None]
        summary[f"control_min_{k}"] = min(cons) if cons else None
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
