"""Whole-list waves through ``repro.top500.predict_fleet``.

The configuration is a TOP500 list: its rows, and the rules that infer
each machine's platform from its row.  Set-up runs the program's
inference over the rows once; the reference infers its own records by
the configuration's rules, so an inference that strays from them shows
in the check.  Every wave predicts every machine of the list, each
under its own drawn scales, with the configuration's fleet tuning and
the per-family calibration.  One checked sample is one wave: every
machine's raw and calibrated Rmax against the reference's.
"""
from __future__ import annotations

import reference
from generator import apply_scales
from harness import spans


class Entry:
    def __init__(self, config: dict, traffic: dict):
        self.rows = config["rows"]
        self.tuning_spec = config["tuning"]
        # the reference's own records of the machines, by the rules the
        # configuration states; the program infers its own in set-up
        self.machines = [reference.infer(r, config["inference"])
                         for r in self.rows]

    def setup(self, metrics=None) -> None:
        """The program's TOP500 inference, once: list rows -> platforms."""
        from repro.top500 import Top500Row, infer_platforms
        from repro.top500.fleet import FleetTuning
        self.platforms = infer_platforms(Top500Row(**r) for r in self.rows)
        self.tuning = FleetTuning(**self.tuning_spec)
        self.metrics = metrics

    def annotate(self):
        """Host spans for the traced run: each machine's tuning, the
        sweep, and the calibration inside ``predict_fleet``."""
        from repro.core import fastsim
        from repro.top500 import calibrate, fleet
        return spans([(fleet, "tune_scenario", "fleet.tune"),
                      (fastsim, "sweep_hpl", "fleet.sweep"),
                      (calibrate, "calibrate_fleet", "fleet.calibrate")])

    def build(self, wave) -> list:
        return [apply_scales(self.platforms[i], s) for i, s in wave]

    def serve(self, platforms) -> dict:
        from repro.top500 import predict_fleet
        rep = predict_fleet(platforms, tuning=self.tuning, calibrate=True,
                            metrics=self.metrics)
        return {"predicted": [e.predicted_tflops for e in rep.entries],
                "calibrated": [e.calibrated_tflops for e in rep.entries],
                "heldout": rep.calibration.heldout_median_abs_err,
                "live_work": float(sum(e.cfg.n_panels * e.cfg.P * e.cfg.Q
                                       for e in rep.entries))}

    def rmax_err_pct(self, wave, answers) -> float:
        """Held-out median |error| against published Rmax, calibrated."""
        return 100.0 * answers["heldout"]

    def live_work(self, wave, answers) -> float:
        """Panel steps times ranks of each machine's tuned proxy run."""
        return answers["live_work"]

    def samples(self, wave, answers) -> list:
        return [([reference.scaled(self.machines[i], s) for i, s in wave],
                 answers)]

    def reference_answers(self, wave, dtype, map_=map) -> dict:
        """What ``serve`` answers for a wave, from the reference."""
        plats = [reference.scaled(self.machines[i], s) for i, s in wave]
        out = self.expected([plats], dtype, map_)[0]
        out["live_work"] = float(sum(
            -(-N // nb) * P * Q for N, nb, P, Q, _ in
            (reference.tune(p, self.tuning_spec) for p in plats)))
        return out

    def expected(self, inputs, dtype, map_=map) -> list:
        out = []
        for plats in inputs:
            ref = reference.fleet(plats, self.tuning_spec, dtype, map_)
            out.append({"predicted": ref["predicted_tflops"],
                        "calibrated": ref["calibrated_tflops"],
                        "heldout": ref["heldout_median_abs_err"]})
        return out

    @staticmethod
    def rel_gap(answer: dict, expected: dict) -> float:
        """Widest relative gap over every machine's raw and calibrated
        Rmax."""
        return max(abs(a - e) / abs(e) for key in ("predicted", "calibrated")
                   for a, e in zip(answer[key], expected[key]))
