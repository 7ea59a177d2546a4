"""The one traffic generator: a traffic file's parameters -> waves.

A traffic file (``traffic/<name>.json``) says which entry of the
service the waves go to and what they hold:

    {"entry": "predict_batch",        # entries/<entry>.py serves a wave
     "machines": 8,                   # requests per wave, cycling
                                      # over the config's machines in
                                      # seeded order; "all": every
                                      # machine once a wave, in order
     "scales": {"link_bw": [0.5, 2]}, # what-if knobs, log-uniform in
                                      # [lo, hi], drawn per request
     "clients": 2,                    # closed-loop clients, each
                                      # sending its next wave when its
                                      # last is answered (default 1)
     "trace_waves": 2}                # waves the profiler traces

A wave is a list of ``(machine index, {knob: scale})``.  The same seed
gives the same waves in the same order, whichever client sends each;
the work of a wave (how many
requests, on which geometry) does not depend on the seed, only the
hardware scales do.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

from reference import SCALE_FIELDS

Wave = List[Tuple[int, Dict[str, float]]]


class Traffic:
    def __init__(self, spec: dict, n_machines: int, seed: int):
        if seed < 0:
            raise ValueError(f"seed must be a whole number >= 0, not {seed}")
        unknown = set(spec.get("scales", {})) - set(SCALE_FIELDS)
        if unknown:
            raise ValueError(f"traffic scales {sorted(unknown)} are not "
                             f"among {sorted(SCALE_FIELDS)}")
        self.spec = spec
        self.n_machines = n_machines
        self.rng = np.random.default_rng(seed)
        machines = spec["machines"]
        self.size = n_machines if machines == "all" else int(machines)

    def _machines(self) -> List[int]:
        """Every wave holds the same machines; the seed only orders them."""
        order = [j % self.n_machines for j in range(self.size)]
        if self.spec["machines"] != "all":
            self.rng.shuffle(order)
        return order

    def next_wave(self) -> Wave:
        wave = []
        for i in self._machines():
            scales = {}
            for knob, (lo, hi) in sorted(self.spec.get("scales",
                                                       {}).items()):
                scales[knob] = math.exp(self.rng.uniform(math.log(lo),
                                                         math.log(hi)))
            wave.append((i, scales))
        return wave

    def unscaled_wave(self) -> Wave:
        """A wave of the same size with every machine as published."""
        return [(j % self.n_machines, {}) for j in range(self.size)]


def apply_scales(platform, scales: Dict[str, float]):
    """A copy of a ``Platform`` with each knob's field multiplied: the
    object form of ``reference.scaled``, so both sides see equal floats."""
    sections = {}
    for knob, s in scales.items():
        section, field = SCALE_FIELDS[knob]
        obj = sections.get(section, getattr(platform, section))
        sections[section] = dataclasses.replace(
            obj, **{field: getattr(obj, field) * s})
    return dataclasses.replace(platform, **sections) if sections \
        else platform
