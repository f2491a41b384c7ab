"""One online learner of a hybrid model's top state-space layer on one chip:
`runtime/online.py` `OnlineTrainer` over the granitemoehybrid period of
`repro.cells.mamba2`, on one stream of token documents.

The configuration's file holds the published config keys and a `model`
section (the layers held, the local heads, batch, optimizer); both go to
the cell and to the reference as one dict.  Weights come from the seed on
the device (`repro.cells.mamba2.init_params`: bf16 as published, the learned
share in float32).  Guard, rewire and checkpoints are off; the trainer's
telemetry exports, so each window ends in its one packed readback, and
windows run back to back (a closed loop).

Set-up drives the first `check_windows` windows from the zero state through
the trainer's own loop (the first compiles), keeping what the check
compares: each window's loss, AdamW's first moment after window 1 and the
parameters after the last.  The measured span is one `run()` of the same
trainer, ended at the deadline.  After the span, untimed, the check reads
the trainer's full state (`OnlineTrainer._ckpt_tree`, what `save` writes),
runs one more window and keeps its loss, first moment and parameters; the
reference restarts from that state, the saved traces held on the host
(`bench/references/mamba2.py` `keep_traces`).
"""
from __future__ import annotations

import jax
import numpy as np

from bench.entries import common as C
from bench.entries.online_stream import _Deadline
from bench.traffic import docs as DOCS

PROGRAM = "online_chunk"   # the name the trainer notes its chunk under
CELL = "mamba2"


def model_of(config: dict) -> dict:
    """The published keys and the `model` section as one dict."""
    out = {k: v for k, v in config.items()
           if k not in ("model", "learner", "assumed", "reduced", "about")
           and not isinstance(v, dict)}
    out.update(config["model"])
    return out


def cell_config(model: dict):
    from repro.cells.mamba2 import GraniteHybridConfig
    lo, hi = model["layers_held"]
    return GraniteHybridConfig(
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        vocab_size=model["vocab_size"],
        layer_types=tuple(model["layer_types"][lo:hi]),
        mamba_n_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"],
        mamba_d_state=model["mamba_d_state"],
        mamba_n_groups=model["mamba_n_groups"],
        mamba_d_conv=model["mamba_d_conv"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        attention_multiplier=model["attention_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        embedding_multiplier=model["embedding_multiplier"],
        logits_scaling=model["logits_scaling"],
        rms_norm_eps=model["rms_norm_eps"],
        local_heads=tuple(model["local_heads"]),
        kv_slots=model["kv_slots"], init_std=model["init_std"])


def flat(tree, prefix: str = "") -> dict:
    """Leaves by path joined with "." (lists by index), on the host."""
    out = {}
    if isinstance(tree, dict):
        items = tree.items()
    else:
        items = enumerate(tree)
    for key, v in items:
        name = f"{prefix}{key}"
        if isinstance(v, (dict, list, tuple)):
            out.update(flat(v, name + "."))
        else:
            out[name] = np.asarray(jax.device_get(v))
    return out


def _f64(tree) -> dict:
    return {k: v.astype(np.float64) for k, v in flat(tree).items()}


def seed_key(seed: int):
    """A key from any whole-number seed (wider than 32 bits too)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class Cell:
    def __init__(self, config: dict, spec: dict, mix: dict, seed: int,
                 traced: bool, workdir):
        self.config = dict(config, seed=seed)
        self.model = model_of(config)
        self.spec, self.mix, self.seed = spec, mix, seed
        self.traced, self.workdir = traced, workdir
        self.k = int(self.model["update_every"])
        self.B = int(self.model["batch"])
        self.n_check = int(spec["check_windows"])
        self.b1 = float(self.model["b1"])

    def setup(self):
        from bench import check as CH
        from repro.cells.mamba2 import init_params
        from repro.core.learner import LearnerSpec, make_learner
        from repro.optim import make_optimizer
        from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig

        mdl = self.model
        self.ref = CH.reference_module(CELL)
        self.cfg = cell_config(mdl)
        learner = make_learner(LearnerSpec(
            engine=self.config["learner"]["engine"], cfg=self.cfg))
        opt = make_optimizer(mdl["optimizer"], lr=mdl["lr"], b1=mdl["b1"],
                             b2=mdl["b2"], eps=mdl["adam_eps"],
                             weight_decay=mdl["weight_decay"])
        params, frozen = jax.jit(lambda key: init_params(self.cfg, key))(
            seed_key(self.seed))
        self.params0 = _f64(params)
        self.frozen = {f"frozen.{k}": v for k, v in flat(frozen).items()}
        self.tel = C.make_telemetry(self.traced, self.workdir, exporters=True)
        stream = DOCS.DocStream(self.mix, self.seed, self.B,
                                int(mdl["vocab_size"]))
        cfg = OnlineTrainerConfig(total_steps=0, update_every=self.k,
                                  log_every=1,
                                  ckpt_dir=str(self.workdir / "ckpt"))
        self.stream = _Deadline(stream, cfg, self.k)
        self.trainer = t = OnlineTrainer(cfg, learner, opt, params, None,
                                         self.stream, telemetry=self.tel,
                                         frozen=frozen)
        del frozen
        self._windows(count=1)
        m1 = jax.device_get(t.opt_state["m"])
        self._windows(count=self.n_check - 1)
        self.setup_stream = {
            "name": "setup", "loss": [r["loss"] for r in t.metrics],
            "grad1": {k: v / (1.0 - self.b1) for k, v in _f64(m1).items()},
            "params": _f64(t.carry["params"]),
            **DOCS.window(stream, 0, self.n_check * self.k)}

    def _windows(self, count=None, seconds=None) -> dict:
        """Whole windows, `count` of them or until `seconds` pass, in one
        `run()`: the record of the span."""
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
        with C.Profile(tdir):
            rec = self._windows(count=n)
        m = self.model
        lo, hi = m["layers_held"]
        rec.update({"streams": 1, "batch": self.B, "update_every": self.k,
                    "model": {key: m[key] for key in (
                        "hidden_size", "intermediate_size", "vocab_size",
                        "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                        "mamba_d_conv", "num_attention_heads",
                        "num_key_value_heads", "kv_slots", "local_heads")},
                    "layer_types": list(m["layer_types"][lo:hi])})
        return rec

    def free(self):
        if self.tel.events is not None:
            self.tel.events.close()
        self.trainer = None

    # -- what the check compares -----------------------------------------------

    def check_record(self) -> list:
        """The set-up windows from the zero state, and one window restarted
        from the state the measured span left (run here, untimed)."""
        model = dict(self.model)
        setup = {"part": "", "model": model,
                 "params0": dict(self.params0, **self.frozen), "masks": {},
                 "windows": self.n_check, "streams": [self.setup_stream]}
        return [setup, self._restart(model)]

    def _restart(self, model: dict) -> dict:
        t = self.trainer
        tree = t._ckpt_tree()
        carry = tree["carry"]
        pos, count = int(tree["pos"]), t.update
        start = {"params": _f64(carry["params"]),
                 "m": _f64(tree["opt"]["m"]), "v": _f64(tree["opt"]["v"]),
                 "count": count,
                 "state": {k: (v.astype(np.float64)
                               if np.issubdtype(v.dtype, np.floating) else v)
                           for k, v in flat(carry["h"]).items()},
                 "traces": self.ref.keep_traces(lambda: flat(carry["tr"]))}
        del tree, carry
        self._windows(count=1)
        m_after = _f64(t.opt_state["m"])
        stream = {"name": f"restart@{pos}", "start": start,
                  "loss": [t.metrics[-1]["loss"]],
                  "grad1": {k: (v - self.b1 * start["m"][k]) / (1.0 - self.b1)
                            for k, v in m_after.items()},
                  "params": _f64(t.carry["params"]),
                  **DOCS.window(self.stream.stream, pos, self.k)}
        return {"part": "restart", "model": model,
                "params0": dict(start["params"], **self.frozen),
                "masks": {}, "windows": 1, "streams": [stream]}
