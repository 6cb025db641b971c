"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's numbers.

What it reads: the device planes (`/device:TPU:<n>`) and their op lines,
whose events carry the HLO op and the `jax.named_scope` path it was
traced under; and, on the host, the thread that holds the harness's
`bench.*` annotations around `submit` and `result`. Out of them:

* the traced window: the first `bench.*` annotation's start to the last
  one's end;
* busy time: the union of the intervals in which an op ran on a device,
  inside the window, averaged over the devices;
* device time per named scope, summed over ops: the renderer's
  `rtnerf.*` and the field-evaluation scopes of the cell's architecture
  (`SCOPES` of `bench/archs/<arch>.py`), which also count in
  `rtnerf.field_eval`;
* the longest device ops, by scope, and the longest idle gaps, each named
  by the innermost host event that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

OP_LINES = ("XLA Ops",)
HOST_MARK = "bench."
SCOPES = ("rtnerf.intersect", "rtnerf.compact", "rtnerf.field_eval",
          "rtnerf.composite", "rtnerf.scatter")
FIELD_EVAL = "rtnerf.field_eval"


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def scope_of(text: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of `scopes` named in an op's scope path, if any."""
    best, at = None, -1
    for sc in scopes:
        i = text.rfind(sc)
        if i > at:
            best, at = sc, i
    return best


@dataclasses.dataclass
class Op:
    name: str
    scope: Optional[str]
    start: float            # seconds on the trace clock
    end: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices
    n_devices: int
    scope_s: Dict[str, float]          # device seconds per scope, summed
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> Dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def _short(op_text: str) -> str:
    """An HLO op's name out of its text ("%fusion.12 = f32[...] ...")."""
    return op_text.split(" = ", 1)[0].lstrip("%")


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, bytes for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            if b < 0x80:
                return v
            shift += 7
    while i < n:
        key = varint()
        field, wt = key >> 3, key & 7
        if wt == 0:
            yield field, wt, varint()
        elif wt == 2:
            ln = varint()
            yield field, wt, buf[i:i + ln]
            i += ln
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an XSpace")


def op_scopes(path: str, scopes: Sequence[str]
              ) -> Dict[str, Optional[str]]:
    """HLO op text -> the named scope its op path names, from the device
    planes' event metadata (XSpace: planes = 1; XPlane: name = 2,
    event_metadata = 4; XEventMetadata: name = 2, stats = 5; XStat:
    str_value = 5). The profiler keeps each op's `jit(...)/.../scope/op`
    path there, which the event API does not show."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Optional[str]] = {}
    for fld, _, plane in _fields(space):
        if fld != 1:
            continue
        name, metas = "", []
        for f2, _, v in _fields(plane):
            if f2 == 2:
                name = v.decode("utf-8", "replace")
            elif f2 == 4:
                metas.append(v)
        if not name.startswith("/device:") or "CPU" in name:
            continue
        for entry in metas:
            for f3, _, meta in _fields(entry):
                if f3 != 2:
                    continue
                op, texts = None, []
                for f4, _, v in _fields(meta):
                    if f4 == 2:
                        op = v.decode("utf-8", "replace")
                    elif f4 == 5:
                        texts += [x.decode("utf-8", "replace")
                                  for f5, wt, x in _fields(v)
                                  if f5 == 5 and wt == 2]
                if op is not None:
                    out[op] = scope_of(" ".join(texts), scopes)
    return out


def read(path: str, field_scopes: Sequence[str]):
    """(device ops per device, host events (name, start, end)) of one
    `.xplane.pb`, times in seconds on the trace's clock; each op carries
    the innermost of the renderer's and `field_scopes` it ran under."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    named = SCOPES + tuple(field_scopes)
    scopes = op_scopes(path, named)
    devices: List[List[Op]] = []
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops: List[Op] = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    sc = scopes.get(ev.name) or scope_of(ev.name, named)
                    ops.append(Op(_short(ev.name), sc, s,
                                  s + ev.duration_ns * 1e-9))
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events]
                # only the thread that drove the window: its line holds
                # the harness's annotations
                if any(n.startswith(HOST_MARK) for n, _, _ in evs):
                    host.extend(evs)
    return devices, host


def leaves(ops: List[Op]) -> List[Op]:
    """The ops that hold no other op of their line inside them: a control
    op (the scan's `while`) spans its body's ops and is left out of the
    per-op and per-scope sums, though not out of busy time."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= o.end or nxt.end > o.end:
            out.append(o)
    return out


def reduce(devices: List[List[Op]], host,
           field_scopes: Sequence[str]) -> Optional[Summary]:
    """The benchmark's numbers from one trace; None without device ops or
    without the harness's annotations. Time in `field_scopes` counts in
    `rtnerf.field_eval` too."""
    marks = [(s, e) for n, s, e in host if n.startswith(HOST_MARK)]
    if not devices or not marks:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    busy, scope_s, per_op = [], {}, {}
    for ops in devices:
        busy.append(merge(clip([(o.start, o.end) for o in ops], lo, hi)))
        for o in leaves(ops):
            d = min(o.end, hi) - max(o.start, lo)
            if d <= 0:
                continue
            if o.scope is not None:
                scope_s[o.scope] = scope_s.get(o.scope, 0.0) + d
                if o.scope in field_scopes:
                    scope_s[FIELD_EVAL] = scope_s.get(FIELD_EVAL, 0.0) + d
            key = f"{o.scope or '-'}:{o.name}"
            per_op[key] = per_op.get(key, 0.0) + d
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(devices)

    # idle gaps on the first device, named by the innermost host event
    # (shortest one) that covers the gap's middle
    gaps = []
    prev = lo
    for s, e in busy[0] + [(hi, hi)]:
        if s > prev:
            mid = 0.5 * (prev + s)
            cover = [(he - hs, n) for n, hs, he in host
                     if hs <= mid <= he]
            gaps.append((min(cover)[1] if cover else "-", s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])
    return Summary(hi - lo, busy_s, len(devices), scope_s, top, gaps)


def reduce_dir(d: str, field_scopes: Sequence[str]
               ) -> Optional[Summary]:
    paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    return reduce(*read(sorted(paths)[-1], field_scopes), field_scopes)
