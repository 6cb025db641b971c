"""What the run is on: the platform check and the table of peaks."""
from __future__ import annotations

import json
import os
from typing import Dict, List


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def devices(chips: int, rehearse: bool) -> List:
    """The first `chips` devices. Without an accelerator (or with fewer
    chips than asked for) this raises, unless the run is a rehearsal."""
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform == "cpu":
            raise NoChip("JAX found no accelerator (platform cpu)")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips] if len(devs) >= chips else devs[:1]


def describe(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(root: str, kind: str) -> Dict:
    """Published peaks of one chip of this kind. An unknown kind is an
    error, not a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
