"""The one traffic generator: reads a mix's data file and the seed, returns work.

A ``replay`` mix replays logged evidence through the batch decide: a fixed
``batches_per_network`` batches of ``frames_per_chip x chips`` rows per
network, cycled.  Evidence rows come from the reference's own sampler of each
network, so every seed offers batches of the same shapes, in the same launch
order, with rows drawn as the network predicts its sensors fire.

A ``served`` mix is a vehicle's camera ticks, open loop: ``cameras`` cameras
at ``camera_hz`` each, evenly staggered, so tick ``i`` is due ``i / (cameras
x camera_hz)`` seconds into the window.  A tick decides each of its objects
with every ``object_networks`` network and the scene once with every
``scene_networks`` network.  Objects per tick follow a Poisson law of mean
``objects_per_tick_mean``; every seed gets the same multiset of counts (the
law's quantiles at evenly spaced levels) in its own order, so the seed
changes the order and the evidence, never the amount of work.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

from chipbench.reference import Network


def replay_batches(mix: dict, nets: Dict[str, Network], tenants: List[str],
                   chips: int, seed: int) -> Dict[str, List[np.ndarray]]:
    """``batches_per_network`` evidence batches of ``frames_per_chip * chips`` rows per network."""
    rng = np.random.default_rng([seed, 2])
    b = int(mix["frames_per_chip"]) * int(chips)
    return {
        name: [nets[name].sample_rows(rng, b) for _ in range(int(mix["batches_per_network"]))]
        for name in tenants
    }


@dataclasses.dataclass
class Tick:
    """One camera tick: when it is due (seconds into the window) and its frames by network."""

    due_s: float
    objects: int
    frames: List[Tuple[str, np.ndarray]]   # (network, evidence rows), one submit each


def poisson_quantiles(mean: float, n: int) -> np.ndarray:
    """The Poisson(``mean``) quantiles at levels ``(i + 0.5) / n``, i < n, ascending."""
    levels = (np.arange(n) + 0.5) / n
    pmf = math.exp(-mean)
    cdf = [pmf]
    while n and cdf[-1] < levels[-1]:
        pmf *= mean / len(cdf)
        cdf.append(cdf[-1] + pmf)
    return np.searchsorted(np.asarray(cdf), levels)


def tick_schedule(mix: dict, nets: Dict[str, Network], seconds: float,
                  seed: int) -> List[Tick]:
    """Every tick due in a window of ``seconds``, with its evidence rows, from the seed."""
    rng = np.random.default_rng([seed, 4])
    rate = int(mix["cameras"]) * float(mix["camera_hz"])
    n = int(math.ceil(seconds * rate))
    objects = rng.permutation(poisson_quantiles(float(mix["objects_per_tick_mean"]), n))
    per_object, per_tick = list(mix["object_networks"]), list(mix["scene_networks"])
    total = int(objects.sum())
    rows = {t: nets[t].sample_rows(rng, total) for t in per_object}
    rows.update({t: nets[t].sample_rows(rng, n) for t in per_tick})
    ticks, at = [], 0
    for i, k in enumerate(objects.tolist()):
        frames = [(t, rows[t][at:at + k]) for t in per_object] if k else []
        frames += [(t, rows[t][i:i + 1]) for t in per_tick]
        ticks.append(Tick(i / rate, k, frames))
        at += k
    return ticks
