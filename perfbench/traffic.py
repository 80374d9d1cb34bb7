"""The one traffic generator: periodic camera streams in groups, open loop.

A traffic mix is a JSON file under ``traffic/``: ``groups`` of
``hp_per_group`` HP and ``lp_per_group`` LP tasks, each releasing one frame every 1000/``jps`` ms with deadline equal
to the period (paper Table II), plus the server partition the cell runs
on and the run's warm-up, grace and sampling sizes.

Phases are stratified: one phase per stratum of the period
[i T/N, (i+1) T/N), uniform within it, with the strata dealt to the tasks
in a seeded order. Every seed offers the same load spread over the
period, in another arrangement. This departs from the paper's draw,
uniform in [0, T) (section V), which lets a seed bunch releases together
and so changes the work from seed to seed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

HP, LP = "hp", "lp"


@dataclasses.dataclass(frozen=True)
class Stream:
    name: str
    priority: str     # HP or LP
    index: int        # position in the task list: picks the task's weights
    phase_ms: float
    period_ms: float

    def releases(self, until_ms: float) -> np.ndarray:
        """Due times of every frame due before ``until_ms``."""
        n = int(np.ceil((until_ms - self.phase_ms) / self.period_ms))
        return self.phase_ms + self.period_ms * np.arange(max(n, 0))


def streams(traffic: Dict, prefix: str, rng: np.random.Generator
            ) -> List[Stream]:
    period = 1000.0 / traffic["jps"]
    kinds = []
    for g in range(traffic["groups"]):
        kinds += [(f"{prefix}-g{g}-hp{k}", HP)
                  for k in range(traffic["hp_per_group"])]
        kinds += [(f"{prefix}-g{g}-lp{k}", LP)
                  for k in range(traffic["lp_per_group"])]
    n = len(kinds)
    phases = (rng.permutation(n) + rng.uniform(0.0, 1.0, n)) * period / n
    return [Stream(name, prio, i, float(ph), period)
            for i, ((name, prio), ph) in enumerate(zip(kinds, phases))]
