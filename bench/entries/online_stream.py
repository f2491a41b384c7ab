"""One online learner on one chip: `runtime/online.py` `OnlineTrainer` on
one endless step-keyed stream, the paper's setting.

The learner is the configuration's, with the workload's `learner` settings
over it (`bench/run.py`); guard, rewire and checkpoints are off.  The
trainer's telemetry exports (events into the run's work directory), so each
window ends in the trainer's one packed readback of its MetricPack, which
brings the window's loss and sparsities to the host: a stream whose every
window is monitored, as `launch/train.py --online --metrics-dir` runs it.
(Without exporters the trainer reads the loss, alpha, beta and overflow
back one by one on each logged window.)  Windows run back to back: a closed
loop.

Set-up drives the first `check_windows` windows from the zero state through
the trainer's own loop (the first compiles), keeping what the check
compares: each window's loss, AdamW's first moment after window 1 and the
parameters after the last.  The measured span is one `run()` of the same
trainer, whose stream ends at the deadline: the window that starts past it
is the span's last.  After the span, untimed, the check takes the trainer's
full state (the tree `OnlineTrainer.save` writes), runs one more window and
keeps its loss, first moment and parameters; the reference restarts from
that state, the influence turned into its layout by
`bench/model.py` `dense_influence`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as M
from bench.entries import common as C
from bench.traffic import generator as G

PROGRAM = "online_chunk"   # the name the chunk's stage map is noted under


class _Deadline:
    """The trainer's stream, ended at a deadline: the first window that
    starts at or past `at` is the run's last."""

    def __init__(self, stream, cfg, k: int):
        self.stream, self.cfg, self.k = stream, cfg, k
        self.at = None

    def __call__(self, step: int):
        if self.at is not None and step % self.k == 0 and C.now() >= self.at:
            self.cfg.total_steps = step + self.k
            self.at = None
        return self.stream(step)


def _canonical(tree) -> dict:
    return {k: np.asarray(v, np.float64)
            for k, v in M.from_program(tree).items()}


class Cell:
    def __init__(self, config: dict, spec: dict, mix: dict, seed: int,
                 traced: bool, workdir):
        self.config = dict(config, seed=seed)
        self.model = config["model"]
        self.spec, self.mix, self.seed = spec, mix, seed
        self.traced, self.workdir = traced, workdir
        self.k = int(self.model["update_every"])
        self.n_check = int(spec["check_windows"])
        self.b1 = float(self.model["b1"])

    def setup(self):
        from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig

        (learner, opt, params, masks, self.params0,
         self.mask) = C.build_learner(self.config)
        self.tel = C.make_telemetry(self.traced, self.workdir, exporters=True)
        _, stream = G.Traffic(self.mix, self.seed, self.model).session(0)
        cfg = OnlineTrainerConfig(total_steps=0, update_every=self.k,
                                  log_every=1,
                                  ckpt_dir=str(self.workdir / "ckpt"))
        self.stream = _Deadline(stream, cfg, self.k)
        self.trainer = t = OnlineTrainer(cfg, learner, opt, params, masks,
                                         self.stream, telemetry=self.tel)
        self._windows(count=1)
        m1 = jax.device_get(t.opt_state["m"])
        self._windows(count=self.n_check - 1)
        self.setup_stream = {
            "name": "setup", "loss": [r["loss"] for r in t.metrics],
            "grad1": {k: v / (1.0 - self.b1)
                      for k, v in _canonical(m1).items()},
            "params": _canonical(jax.device_get(t.carry["params"]))}
        self.setup_stream.update(zip(
            ("xs", "ys"), G.window_inputs(stream, 0, self.n_check * self.k)))
        if self.traced:
            # the chunk's stage map, as the fleet notes its own; the compile
            # is served from the caches
            xs, ys = G.window_inputs(stream, t.step, self.k)
            t.obs.tracer.note_program(PROGRAM, t._chunk, t.carry, t.opt_state,
                                      jnp.asarray(xs), jnp.asarray(ys),
                                      jnp.int32(t.update))

    def _windows(self, count=None, seconds=None) -> dict:
        """Run whole windows, `count` of them or until `seconds` pass, in
        one `run()`: the record of the span."""
        t = self.trainer
        first, logged = t.step, len(t.metrics)
        t0 = C.now()
        if count is not None:
            t.cfg.total_steps = t.step + count * self.k
        else:
            t.cfg.total_steps = 2 ** 62
            self.stream.at = t0 + seconds
        t.run()
        span = C.now() - t0
        losses = np.array([r["loss"] for r in t.metrics[logged:]], float)
        return {"span_s": span, "windows": (t.step - first) // self.k,
                "steps": t.step - first, "attempted": int(losses.size),
                "failed": int((~np.isfinite(losses)).sum()),
                "program": PROGRAM}

    def measure(self, seconds: float) -> dict:
        return self._windows(seconds=seconds)

    def trace(self, n: int, tdir) -> dict:
        logged = len(self.trainer.metrics)
        with C.Profile(tdir):
            rec = self._windows(count=n)
        beta = [r["beta"] for r in self.trainer.metrics[logged:]
                if "beta" in r]
        rec["telemetry"] = {"bwd_sparsity": float(np.mean(beta))} \
            if beta else {}
        rec.update(C.shape(self.model, self.mask, streams=1))
        return rec

    def free(self):
        if self.tel.events is not None:
            self.tel.events.close()
        self.trainer = None

    # -- what the check compares -----------------------------------------------

    def check_record(self) -> list:
        """The set-up windows from the zero state, and one window restarted
        from the state the measured span left (run here, untimed)."""
        setup = {"part": "", "model": self.model, "params0": self.params0,
                 "masks": self.mask, "windows": self.n_check,
                 "streams": [self.setup_stream]}
        return [setup, self._restart()]

    def _restart(self) -> dict:
        t = self.trainer
        saved = jax.device_get(t._ckpt_tree())
        pos, count = int(saved["pos"]), t.update
        self._windows(count=1)
        carry = saved["carry"]
        start = {"params": _canonical(carry["params"]),
                 "m": _canonical(saved["opt"]["m"]),
                 "v": _canonical(saved["opt"]["v"]),
                 "count": count,
                 "state": np.asarray(carry["a"], np.float64),
                 "influence": M.dense_influence(self.model, self.mask,
                                                carry["vals"], carry["idx"])}
        m_after = _canonical(jax.device_get(t.opt_state["m"]))
        xs, ys = G.window_inputs(self.stream.stream, pos, self.k)
        stream = {"name": f"restart@{pos}", "xs": xs, "ys": ys,
                  "start": start, "loss": [t.metrics[-1]["loss"]],
                  "grad1": {k: (v - self.b1 * start["m"][k]) / (1.0 - self.b1)
                            for k, v in m_after.items()},
                  "params": _canonical(jax.device_get(t.carry["params"]))}
        return {"part": "restart", "model": self.model,
                "params0": start["params"], "masks": self.mask, "windows": 1,
                "streams": [stream]}
