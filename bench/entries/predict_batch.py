"""Waves of HPL what-if requests through ``PredictionService.predict_batch``.

One service serves the whole run, as a server serves one client.  Each
request carries its own ``Platform`` (a configuration's machine with the
wave's scales applied) and asks for the machine's published HPL run.
One checked sample is one request: its simulated time against the
reference's.
"""
from __future__ import annotations

import statistics
from typing import List

import reference
from generator import apply_scales
from harness import spans


class Entry:
    def __init__(self, config: dict, traffic: dict):
        self.machines = config["machines"]
        self._rid = 0

    def setup(self, metrics=None) -> None:
        from repro.platforms import Platform
        from repro.serve import PredictionService
        self.platforms = [Platform.from_dict(m) for m in self.machines]
        self.service = PredictionService()

    def annotate(self):
        """Host spans for the traced run: submit (which resolves a
        request), flush, and each family's dispatch inside it."""
        svc = self.service
        return spans([(svc, "submit", "serve.submit"),
                      (svc, "flush", "serve.flush"),
                      (svc, "_dispatch", "serve.dispatch")])

    def build(self, wave) -> list:
        from repro.serve import WorkloadRequest
        reqs = []
        for i, scales in wave:
            reqs.append(WorkloadRequest(
                rid=self._rid, workload="hpl",
                platform=apply_scales(self.platforms[i], scales)))
            self._rid += 1
        return reqs

    def serve(self, reqs) -> List[float]:
        out = self.service.predict_batch(reqs)
        return [out[r.rid]["time_s"] for r in reqs]

    def rmax_err_pct(self, wave, answers) -> float:
        """Median over machines of |predicted - published Rmax| / Rmax."""
        errs = {}
        for (i, _), t in zip(wave, answers):
            sc = self.machines[i]["scale"]
            pred = reference.hpl_flops(sc["hpl_n"]) / t / 1e12
            errs[i] = 100.0 * abs(pred - sc["reported_tflops"]) \
                / sc["reported_tflops"]
        return statistics.median(errs.values())

    def live_work(self, wave, answers) -> float:
        """Panel steps times ranks of the requests' own HPL runs."""
        total = 0
        for i, _ in wave:
            sc = self.machines[i]["scale"]
            P, Q = sc["grid"]
            total += -(-sc["hpl_n"] // sc["hpl_nb"]) * P * Q
        return float(total)

    def samples(self, wave, answers) -> list:
        return [(reference.scaled(self.machines[i], s), t)
                for (i, s), t in zip(wave, answers)]

    def reference_answers(self, wave, dtype, map_=map) -> List[float]:
        """What ``serve`` answers for a wave, from the reference."""
        return self.expected([reference.scaled(self.machines[i], s)
                              for i, s in wave], dtype, map_)

    def expected(self, inputs, dtype, map_=map) -> list:
        return [float(t[0]) for t in map_(reference.published_times,
                                         [[p] for p in inputs],
                                         [dtype] * len(inputs))]

    @staticmethod
    def rel_gap(answer: float, expected: float) -> float:
        return abs(answer - expected) / abs(expected)
