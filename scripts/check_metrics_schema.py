#!/usr/bin/env python
"""Validate a `repro.obs/v1` metrics snapshot (CI metrics-smoke gate).

Checks the JSON envelope produced by `serve --metrics-dump` or the
`/metrics.json` endpoint against the schema contract documented in
docs/observability.md:

  * envelope: `schema == "repro.obs/v1"`, numeric `ts_unix_s`, a
    `metrics` object with `counters` / `gauges` / `histograms` maps;
  * every counter/gauge snapshot has a numeric `value` (counters >= 0);
  * every histogram snapshot has integer `count`/`window_len`/`maxlen`,
    numeric `sum`/`max`/`last`/`mean`/`p50`/`p95`/`p99`, with
    `window_len <= min(count, maxlen)` and `p50 <= p95 <= p99 <= max`
    (when the window is non-empty);
  * flat names parse as `name` or `name{k=v,...}`.

`--expect-counter NAME` / `--expect-gauge NAME` / `--expect-histogram
NAME` (repeatable) assert a metric of that base name exists — CI uses
them to pin the serving-stack names (engine_views_served,
request_stage_s, fleet_requests_total, ...) so a rename cannot land
without updating the docs and this gate. `--expect-prefix-complete
PREFIX` additionally flags metrics under that prefix that are NOT
pinned — so a new fleet_* family cannot land undocumented either.
Pin violations are collected and reported as one readable diff
(`- missing ...` / `+ unexpected ...`), not a bare first-failure assert;
structural envelope violations still exit on first hit.

    python scripts/check_metrics_schema.py /tmp/obs.json \
        --expect-counter engine_views_served \
        --expect-counter engine_samples_processed \
        --expect-counter engine_eval_steps \
        --expect-histogram engine_latency_s \
        --expect-histogram store_prepare_s \
        --expect-counter fleet_requests_total \
        --expect-prefix-complete fleet_
"""
from __future__ import annotations

import argparse
import json
import re
import sys

FLAT = re.compile(r"^[A-Za-z_:][A-Za-z0-9_:.]*(\{[^{}]*\})?$")


def fail(msg: str):
    sys.exit(f"metrics schema violation: {msg}")


def base_name(flat: str) -> str:
    return flat.split("{", 1)[0]


def need_num(obj, key, where, *, integer=False):
    v = obj.get(key)
    ok = isinstance(v, int) if integer \
        else isinstance(v, (int, float)) and not isinstance(v, bool)
    if not ok:
        fail(f"{where}: '{key}' must be {'an integer' if integer else 'a number'}, got {v!r}")
    return v


def check(snap, expect_counters, expect_gauges, expect_histograms,
          prefix_complete):
    if snap.get("schema") != "repro.obs/v1":
        fail(f"schema must be 'repro.obs/v1', got {snap.get('schema')!r}")
    need_num(snap, "ts_unix_s", "envelope")
    metrics = snap.get("metrics")
    if not isinstance(metrics, dict):
        fail("'metrics' must be an object")
    for kind in ("counters", "gauges", "histograms"):
        if not isinstance(metrics.get(kind), dict):
            fail(f"metrics.{kind} must be an object")

    for kind in ("counters", "gauges"):
        for flat, m in metrics[kind].items():
            if not FLAT.match(flat):
                fail(f"{kind} name {flat!r} does not parse")
            v = need_num(m, "value", f"{kind}[{flat}]")
            if kind == "counters" and v < 0:
                fail(f"counters[{flat}]: negative value {v}")

    for flat, h in metrics["histograms"].items():
        where = f"histograms[{flat}]"
        if not FLAT.match(flat):
            fail(f"histogram name {flat!r} does not parse")
        count = need_num(h, "count", where, integer=True)
        wlen = need_num(h, "window_len", where, integer=True)
        maxlen = need_num(h, "maxlen", where, integer=True)
        for k in ("sum", "max", "last", "mean", "p50", "p95", "p99"):
            need_num(h, k, where)
        if wlen > maxlen:
            fail(f"{where}: window_len {wlen} > maxlen {maxlen}")
        if wlen > count:
            fail(f"{where}: window_len {wlen} > all-time count {count}")
        if wlen > 0 and not (h["p50"] <= h["p95"] <= h["p99"]
                             <= h["max"] + 1e-9):
            fail(f"{where}: percentiles not ordered "
                 f"(p50={h['p50']} p95={h['p95']} p99={h['p99']} "
                 f"max={h['max']})")

    # -- name pins: collect everything, fail once with a readable diff --
    have = {kind: {base_name(f) for f in metrics[kind]}
            for kind in ("counters", "gauges", "histograms")}
    expected = {"counters": set(expect_counters),
                "gauges": set(expect_gauges),
                "histograms": set(expect_histograms)}
    diff = []
    for kind in ("counters", "gauges", "histograms"):
        for name in sorted(expected[kind] - have[kind]):
            diff.append(f"- missing {kind[:-1]} {name}")
    pinned = set().union(*expected.values())
    for prefix in prefix_complete:
        for kind in ("counters", "gauges", "histograms"):
            for name in sorted(have[kind]):
                if name.startswith(prefix) and name not in pinned:
                    diff.append(f"+ unexpected {kind[:-1]} {name} "
                                f"(matches --expect-prefix-complete "
                                f"{prefix!r} but is not pinned)")
    if diff:
        sys.exit("metrics schema violation: pinned names do not match "
                 "the snapshot:\n  " + "\n  ".join(diff)
                 + "\n(update the --expect-* pins AND "
                 "docs/observability.md together)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("snapshot", help="snapshot JSON path, or '-' for stdin")
    ap.add_argument("--expect-counter", action="append", default=[],
                    metavar="NAME", help="require a counter of this base "
                    "name (repeatable)")
    ap.add_argument("--expect-gauge", action="append", default=[],
                    metavar="NAME", help="require a gauge of this base "
                    "name (repeatable)")
    ap.add_argument("--expect-histogram", action="append", default=[],
                    metavar="NAME", help="require a histogram of this base "
                    "name (repeatable)")
    ap.add_argument("--expect-prefix-complete", action="append",
                    default=[], metavar="PREFIX",
                    help="flag metrics under PREFIX that are not pinned "
                    "by an --expect-* flag (repeatable)")
    args = ap.parse_args()
    if args.snapshot == "-":
        snap = json.load(sys.stdin)
    else:
        with open(args.snapshot) as f:
            snap = json.load(f)
    check(snap, args.expect_counter, args.expect_gauge,
          args.expect_histogram, args.expect_prefix_complete)
    n = sum(len(snap["metrics"][k]) for k in ("counters", "gauges",
                                              "histograms"))
    print(f"ok: repro.obs/v1 snapshot with {n} metrics "
          f"({len(snap['metrics']['histograms'])} histograms)")


if __name__ == "__main__":
    main()
