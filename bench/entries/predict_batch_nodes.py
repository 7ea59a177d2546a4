"""Waves of HPL what-if requests through ``PredictionService.predict_batch``
for machines that put several ranks on a node: the ``predict_batch``
client, checked against the node-aware reference (``reference_nodes``),
which prices each message by whether it leaves its node under the
record's ranks per node and process mapping."""
from __future__ import annotations

from pathlib import Path

import reference_nodes
from harness import load_module

_base = load_module(Path(__file__).with_name("predict_batch.py"))


class Entry(_base.Entry):
    def expected(self, inputs, dtype, map_=map) -> list:
        return [float(t[0]) for t in map_(reference_nodes.published_times,
                                         [[p] for p in inputs],
                                         [dtype] * len(inputs))]
