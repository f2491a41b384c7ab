"""Drive a cell through the harness's own functions on the CPU, at a size a
test can hold: the check for a chip is skipped."""
from __future__ import annotations

import jax

from bench import run as R

TINY = {"n16-fleet1024": {"model": {"n_hidden": 8, "batch": 4,
                                  "update_every": 2},
                        "learner": {}, "spec": {"slots": 3,
                                                "check_span_sessions": 3}},
        "n16-stream": {"model": {"n_hidden": 8, "batch": 4,
                                 "update_every": 2},
                       "learner": {}, "spec": {"trace_windows": 4}}}


def resolve_tiny(workload: str) -> dict:
    r = R.resolve(workload)
    t = TINY[workload]
    r["config"]["model"].update(t["model"])
    r["config"]["learner"].update(t["learner"])
    r["spec"].update(t["spec"])
    return r


def run_tiny(workload: str, seed: int = 2 ** 33 + 17, seconds: float = 0.3,
             trace: bool = False) -> dict:
    return R.run_cell(resolve_tiny(workload), seed, seconds, trace,
                      jax.devices())
