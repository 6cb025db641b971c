"""The one traffic generator: camera poses drawn from a mix's data file.

A mix (`bench/traffic/<name>.json`) gives the view size and intrinsics,
the sphere the camera origins lie on (radius, azimuth and elevation
ranges in radians) and what each view looks at: `"origin"` (the scene
centre) or `"occupied_cube"` (the centre of an occupied cube drawn
uniformly: a gaze point on the geometry). `warmup_seed` fixes the views
that warm the server up, so that every run's set-up does the same work.

The window serves passes over the mix's `pool` ({"seed": s, "views":
k}): the first k poses of seed s's stream, in one order drawn from the
run's seed and repeated pass after pass. Every seed gives the window the
same work, so that a run's rate does not depend on which poses its seed
drew. Repeating one order makes a run's passes alike: the engine's
adaptive pair budget, which shrinks after three views in a row fill
under a quarter of it, shrinks in a seed's first pass or not at all.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List

import numpy as np

from bench import geometry
from bench.inputs import seed_words

WINDOW_STREAM = 1
SAMPLE_STREAM = 2


@dataclasses.dataclass(frozen=True)
class Pose:
    origin: np.ndarray      # (3,) float32
    c2w: np.ndarray         # (3, 3) float32
    focal: float
    h: int
    w: int

    @property
    def n_rays(self) -> int:
        return self.h * self.w

    def rays(self):
        return geometry.pixel_rays(self.c2w, self.origin, self.focal,
                                   self.h, self.w)


def poses(mix: dict, centers: np.ndarray, seed: int,
          stream: int = WINDOW_STREAM) -> Iterator[Pose]:
    """Endless pose stream of one mix for one (seed, stream)."""
    rng = np.random.default_rng(seed_words(seed, stream))
    o, v = mix["origin"], mix["view"]
    while True:
        az = rng.uniform(*o["azimuth"])
        el = rng.uniform(*o["elevation"])
        origin = (o["radius"] * np.array(
            [np.cos(az) * np.cos(el), np.sin(az) * np.cos(el), np.sin(el)])
        ).astype(np.float32)
        if mix["look_at"] == "origin":
            target = np.zeros(3, np.float32)
        elif mix["look_at"] == "occupied_cube":
            target = centers[rng.integers(len(centers))]
        else:
            raise ValueError(f"unknown look_at {mix['look_at']!r}")
        yield Pose(origin, geometry.look_at(origin, target),
                   float(v["focal"]), int(v["h"]), int(v["w"]))


def passes(mix: dict, centers: np.ndarray, seed: int
           ) -> Iterator[List[Pose]]:
    """Endless passes of the window of one mix for one seed."""
    pool = mix["pool"]
    fixed = [p for p, _ in zip(poses(mix, centers, pool["seed"]),
                               range(pool["views"]))]
    rng = np.random.default_rng(seed_words(seed, WINDOW_STREAM))
    order = [fixed[i] for i in rng.permutation(len(fixed))]
    while True:
        yield order


def warmup_poses(mix: dict, centers: np.ndarray) -> Iterator[Pose]:
    return poses(mix, centers, mix["warmup_seed"], WINDOW_STREAM)
