#!/usr/bin/env python
"""Render a serving stage-breakdown table from a metrics snapshot.

Input is a `repro.obs/v1` JSON snapshot — a file written by
`serve --metrics-dump out.json`, or a live scrape:

    PYTHONPATH=src python -m repro.launch.serve --arch rtnerf --demo \
        --res 64 --scene lego --metrics-dump /tmp/obs.json
    python scripts/obs_report.py /tmp/obs.json

    curl -s http://127.0.0.1:9100/metrics.json | \
        python scripts/obs_report.py -

The report has three sections: the per-request stage breakdown (where did
a served view's time go: queue, group, ordering, compaction, render,
deliver — from the `request_stage_s{stage=...}` histograms the tracer
folds every finished request into), the render dispatch-path counts
(`render_dispatch_total{path=...}`: fused kernel vs per-op decode vs
dense), and the headline counters/gauges.
"""
from __future__ import annotations

import argparse
import json
import re
import sys

# canonical lifecycle order (mirrors repro.obs.tracing.REPORT_STAGES
# without importing repro — this script runs against a snapshot file
# alone); warp/mask/composite only appear on temporal-tier delta frames
STAGES = ("warp", "mask", "submit", "queue", "group", "ordering",
          "compaction", "render", "composite", "deliver")

_LABELLED = re.compile(r"^(?P<name>[^{]+)\{(?P<labels>.*)\}$")


def parse_flat(flat: str):
    """'name{k=v,...}' -> (name, {k: v}); bare names -> (name, {})."""
    m = _LABELLED.match(flat)
    if not m:
        return flat, {}
    labels = {}
    for item in m.group("labels").split(","):
        if item:
            k, _, v = item.partition("=")
            labels[k] = v
    return m.group("name"), labels


def stage_table(hists) -> str:
    rows = []
    by_stage = {}
    for flat, snap in hists.items():
        name, labels = parse_flat(flat)
        if name == "request_stage_s" and "stage" in labels:
            by_stage[labels["stage"]] = snap
    known = [s for s in STAGES if s in by_stage]
    extra = sorted(set(by_stage) - set(STAGES))
    if not by_stage:
        return "  (no request_stage_s histograms — tracing off or no " \
               "requests served)"
    hdr = (f"  {'stage':>10s} {'count':>6s} {'p50_ms':>9s} {'p95_ms':>9s} "
           f"{'p99_ms':>9s} {'total_s':>8s}")
    rows.append(hdr)
    rows.append("  " + "-" * (len(hdr) - 2))
    for st in known + extra:
        s = by_stage[st]
        rows.append(f"  {st:>10s} {s['count']:>6d} "
                    f"{s['p50'] * 1e3:>9.2f} {s['p95'] * 1e3:>9.2f} "
                    f"{s['p99'] * 1e3:>9.2f} {s['sum']:>8.3f}")
    return "\n".join(rows)


def dispatch_table(counters) -> str:
    rows = []
    for flat, snap in sorted(counters.items()):
        name, labels = parse_flat(flat)
        if name == "render_dispatch_total" and "path" in labels:
            rows.append(f"  {labels['path']:>10s} {int(snap['value']):>6d}")
    return "\n".join(rows) if rows else "  (no dispatch counts)"


def headline(snapshot) -> str:
    rows = []
    stats = snapshot.get("stats") or {}
    for k in ("views_served", "fps", "latency_p50_s", "latency_p99_s",
              "timeouts", "dropped_pairs", "field_swaps", "evictions",
              "revivals"):
        if k in stats:
            v = stats[k]
            rows.append(f"  {k:>16s} = {v:.3f}" if isinstance(v, float)
                        else f"  {k:>16s} = {v}")
    if not rows:
        counters = snapshot["metrics"]["counters"]
        for flat in sorted(counters):
            rows.append(f"  {flat:>32s} = {counters[flat]['value']:g}")
    return "\n".join(rows) if rows else "  (none)"


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("snapshot",
                    help="path to a repro.obs/v1 JSON snapshot, or '-' "
                         "to read it from stdin")
    args = ap.parse_args()
    if args.snapshot == "-":
        snap = json.load(sys.stdin)
    else:
        with open(args.snapshot) as f:
            snap = json.load(f)
    if snap.get("schema") != "repro.obs/v1":
        sys.exit(f"not a repro.obs/v1 snapshot "
                 f"(schema={snap.get('schema')!r})")

    print("== request stage breakdown ==")
    print(stage_table(snap["metrics"]["histograms"]))
    print("\n== render dispatch paths ==")
    print(dispatch_table(snap["metrics"]["counters"]))
    print("\n== headline ==")
    print(headline(snap))


if __name__ == "__main__":
    main()
