"""The comparison that decides `correct`: served images against the plain
reference, each number beside its limit from `bench/limits/<cell>.json`.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

NUMBERS = ("rmse",)


def gaps(img: np.ndarray, ref: np.ndarray, alts: Dict) -> np.ndarray:
    """Per-channel gaps of one view to the reference. A ray the reference
    allows several colours (`reference.render`) is held to the nearest."""
    d = np.asarray(img, np.float64) - np.asarray(ref, np.float64)
    for r, cols in alts.items():
        cand = np.asarray(img[r], np.float64)[None] - cols
        d[r] = cand[np.argmin(np.abs(cand).max(axis=1))]
    return d


def readings(imgs: Sequence[np.ndarray], refs: Sequence[tuple]
             ) -> Dict[str, float]:
    """Root mean square gap over every pixel and channel of the compared
    views; `refs` holds the reference's (image, alternatives) of each."""
    d = np.concatenate([gaps(a, *r).reshape(-1) for a, r in zip(imgs, refs)])
    if not np.all(np.isfinite(d)):
        return {"rmse": float("inf")}
    return {"rmse": float(np.sqrt(np.mean(d * d)))}


def load_limits(root: str, cell: str) -> Dict[str, float]:
    with open(os.path.join(root, "bench", "limits", f"{cell}.json")) as f:
        lim = json.load(f)
    return {k: float(lim[k]) for k in NUMBERS}


def judge(read: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(correct, [(name, reading, limit)]): correct when every reading is
    at or under its limit."""
    rows = [(k, read[k], limits[k]) for k in NUMBERS]
    return all(v <= lim for _, v, lim in rows), rows
