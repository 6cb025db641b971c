"""The work a view requires, whatever implements it.

`required_samples`: every in-segment sample of every hitting (ray, cube)
pair, with no early termination and nothing dropped, for every
architecture. The arithmetic of one sample and the bytes a view must move
belong to the architecture (`ops_per_sample` and `bytes_per_view` of
`bench/archs/<arch>.py`).
"""
from __future__ import annotations

from bench import geometry


def required_samples(w: dict, h: geometry.Hits) -> int:
    _, mask = geometry.sample_ts(w, h)
    return int(mask.sum())
