"""What the entries share: the program's objects built from a configuration,
the program's telemetry, and the profiler session."""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from bench import model as M


def build_learner(config: dict):
    """(learner, optimizer, program params, program masks, canonical host
    params, canonical masks) as the program's launchers build them, with
    weights from the benchmark (`bench/model.py`)."""
    from repro.core.cells import EGRUConfig
    from repro.core.learner import LearnerSpec, make_learner
    from repro.optim import make_optimizer

    mdl, lrn = config["model"], config["learner"]
    ecfg = EGRUConfig(n_hidden=mdl["n_hidden"], n_in=mdl["n_in"],
                      n_out=mdl["n_out"], kind="gru", gamma=mdl["gamma"],
                      eps=mdl["eps"], batch_size=mdl["batch"], lr=mdl["lr"])
    mask = M.masks(mdl)
    params_c = M.params(mdl, config["seed"], mask)
    layer_masks = M.mask_tree(mdl, mask)
    opt = make_optimizer(mdl["optimizer"], lr=mdl["lr"], b1=mdl["b1"],
                         b2=mdl["b2"], eps=mdl["adam_eps"])
    params = M.to_flat(params_c)
    masks = dict(layer_masks, out=None)
    learner = make_learner(LearnerSpec(
        engine=lrn["engine"], cfg=ecfg, backend=lrn["backend"],
        col_compact=lrn["col_compact"], capacity=lrn["capacity"]))
    host = {k: np.asarray(v, np.float64) for k, v in
            jax.device_get(params_c).items()}
    return learner, opt, params, masks, host, mask


def make_telemetry(traced: bool, workdir: Path, exporters: bool = False):
    """The program's telemetry: inert for a timed run unless `exporters`;
    for a traced run, exporters on (so the update chunk packs its
    MetricPack) and spans on the profiler's clock."""
    from repro.obs import Telemetry
    from repro.obs.events import EventLog
    from repro.obs.registry import Registry
    from repro.obs.trace import Tracer

    exporters = exporters or traced
    events = EventLog(workdir / "events.jsonl") if exporters else None
    return Telemetry(Registry(), events,
                     Tracer(enabled=traced, jax_annotations=traced),
                     workdir if exporters else None, "bench", None)


class Profile:
    """A profiler session into `tdir`, host Python tracing off."""

    def __init__(self, tdir: Path):
        self.tdir = tdir

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.tdir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


def window_record(stamps: list, t0: float, steps_per_window: list,
                  losses: list) -> dict:
    """Completed-window timestamps -> the measured span (from `t0` to the
    last completion) and its counts."""
    finite = np.isfinite(np.asarray(losses, float))
    return {"span_s": float(stamps[-1] - t0), "windows": len(stamps),
            "steps": int(sum(steps_per_window)),
            "attempted": int(finite.size), "failed": int((~finite).sum())}


def now() -> float:
    return time.perf_counter()


def shape(model: dict, mask: dict, streams: int) -> dict:
    """Sizes the metric readers need.  Pc, the live influence columns, is
    every kept input and recurrent weight plus the three gates' biases and
    the thresholds (one column per parameter that the masks keep)."""
    n = model["n_hidden"]
    nnz = int(sum(int(v.sum()) for v in mask.values()))
    return {"streams": streams, "batch": model["batch"], "n": n,
            "n_in": model["n_in"], "n_out": model["n_out"],
            "nnz_weights": nnz, "Pc": nnz + 3 * n + n,
            "update_every": int(model["update_every"])}


def mean_pack(packs: list) -> dict:
    """Mean of each MetricPack field over the given windows (and sessions),
    leaving out the fields an engine marks not applicable (NaN)."""
    out = {}
    for key in set().union(*(p.keys() for p in packs if p)):
        vals = [float(p[key]) for p in packs if p and key in p]
        vals = [v for v in vals if np.isfinite(v)]
        if vals:
            out[key] = float(np.mean(vals))
    return out
