"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and makes, from the run's seed, the streams a cell feeds its learners.

A mix is parameters only:

  kind        "sessions": a queue of sessions, each its own stream, that
              join free slots of a fleet and leave after some update
              windows (a saturated closed-loop drain); "stream": one
              endless stream (`session(0)`), that never leaves.
  inputs      "spiral": the paper's 2-D spirals (Sec. 6), one sequence of
              `seq_len` steps per example, label = orientation.
  session_windows_mean  ("sessions") after every window a fixed share
                       1 / mean of the live sessions leaves (the running
                       total rounded down, so every seed has the same count
                       in every window), the leavers picked by the seed: a
                       session's length is then geometric with that mean,
                       and every seed offers the same work per window.
  spirals, seq_len, noise  size of the spiral set, steps per sequence,
                       observation noise.

`Traffic(mix, seed, model)`: `session(i)` gives the i-th session's id and
stream, `leavers(w, live)` the live sessions that leave after window w.
Every stream is step-keyed, `stream(step) -> (x [B, n_in] f32, y [B]
int32)`, so the plain reference can replay exactly what a learner saw.
"""
from __future__ import annotations

import math

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def spiral_set(seed: int, n: int, seq_len: int, noise: float):
    """The paper's spiral task (a copy of the program's `data/spiral.py`
    generator): -> xs [n, seq_len, 2] f32, labels [n] int32 (1 = CCW)."""
    rng = _rng(seed, 2)
    labels = rng.integers(0, 2, size=n).astype(np.int32)
    sign = np.where(labels == 1, 1.0, -1.0)
    theta0 = rng.uniform(0, 2 * np.pi, size=n)
    omega = rng.uniform(0.25, 0.55, size=n) * sign
    r0 = rng.uniform(0.1, 0.3, size=n)
    r1 = rng.uniform(0.8, 1.2, size=n)
    t = np.arange(seq_len)[None, :]
    r = r0[:, None] + (r1 - r0)[:, None] * t / (seq_len - 1)
    ang = theta0[:, None] + omega[:, None] * t
    xs = np.stack([r * np.cos(ang), r * np.sin(ang)], axis=-1)
    xs += noise * rng.standard_normal(xs.shape)
    return xs.astype(np.float32), labels


class SpiralStream:
    """One session's stream: every `seq_len` steps a fresh batch of B
    sequences drawn (by the session's own seed) from the shared set."""

    def __init__(self, xs_all, ys_all, session_seed: int, B: int):
        self.xs_all, self.ys_all = xs_all, ys_all
        self.seed, self.B = session_seed, B
        self.T = xs_all.shape[1]
        self._block = (-1, None, None)

    def __call__(self, step: int):
        s, t = divmod(step, self.T)
        if self._block[0] != s:
            sel = _rng(self.seed, s).integers(0, self.ys_all.shape[0],
                                              size=self.B)
            self._block = (s, self.xs_all[sel], self.ys_all[sel])
        _, xb, yb = self._block
        return xb[:, t], yb


def window_inputs(stream, start: int, steps: int):
    """[steps, B, n_in] inputs and [steps, B] labels from a step-keyed
    stream, for the reference."""
    xs, ys = zip(*(stream(start + i) for i in range(steps)))
    return np.stack(xs), np.stack(ys)


# ---------------------------------------------------------------------------
# mixes
# ---------------------------------------------------------------------------

class Traffic:
    """The sessions of one mix and seed (see the module docstring)."""

    def __init__(self, mix: dict, seed: int, model: dict):
        self.mix, self.seed = mix, seed
        self.B = model["batch"]
        if mix["kind"] not in ("sessions", "stream"):
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        if mix["inputs"] != "spiral":
            raise ValueError(f"unknown inputs {mix['inputs']!r}")
        self.mean = float(mix.get("session_windows_mean", math.inf))
        self.xs_all, self.ys_all = spiral_set(
            seed, int(mix["spirals"]), int(mix["seq_len"]),
            float(mix["noise"]))

    def session(self, i: int):
        sseed = int(_rng(self.seed, 3, i).integers(0, 2 ** 62))
        return f"s{i}", SpiralStream(self.xs_all, self.ys_all, sseed, self.B)

    def leave_count(self, w: int, live: int) -> int:
        """How many of `live` sessions leave after window w."""
        return (math.floor((w + 1) * live / self.mean)
                - math.floor(w * live / self.mean))

    def leavers(self, w: int, live: list) -> list:
        """The sessions of `live` (in a fixed order) that leave after
        window w."""
        k = self.leave_count(w, len(live))
        pick = _rng(self.seed, 4, w).choice(len(live), size=k, replace=False)
        return [live[i] for i in sorted(pick)]
