"""The one traffic generator: reads a mix's data file and the seed, returns work.

A ``replay`` mix replays logged evidence through the batch decide: a fixed
``batches_per_network`` batches of ``frames_per_chip x chips`` rows per
network, cycled.  Evidence rows come from the reference's own sampler of each
network, so every seed offers batches of the same shapes, in the same launch
order, with rows drawn as the network predicts its sensors fire.
"""

from __future__ import annotations

from typing import Dict, List

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
