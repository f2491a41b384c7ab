"""The paper's synthetic task (Sec. 6): 2-D spirals unwinding over time,
classified clockwise vs anti-clockwise.

"The dataset consisted of 10,000 randomly generated spirals of 17 timesteps
length assigned to one of the two classes depending on the orientation."

Exact generator parameters were unpublished; ours: radius grows linearly
from r0 to r1 over T steps while the angle advances by a per-sample angular
velocity; orientation sign defines the label; Gaussian noise added.
"""
from __future__ import annotations

import numpy as np


def spiral_dataset(n_samples: int = 10_000, T: int = 17, noise: float = 0.05,
                   seed: int = 0):
    """-> xs [N, T, 2] float32, labels [N] int32 (0 = CW, 1 = CCW)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, size=n_samples).astype(np.int32)
    sign = np.where(labels == 1, 1.0, -1.0)
    theta0 = rng.uniform(0, 2 * np.pi, size=n_samples)
    omega = rng.uniform(0.25, 0.55, size=n_samples) * sign     # rad / step
    r0 = rng.uniform(0.1, 0.3, size=n_samples)
    r1 = rng.uniform(0.8, 1.2, size=n_samples)
    t = np.arange(T)[None, :]
    r = r0[:, None] + (r1 - r0)[:, None] * t / (T - 1)
    ang = theta0[:, None] + omega[:, None] * t
    xs = np.stack([r * np.cos(ang), r * np.sin(ang)], axis=-1)
    xs += noise * rng.standard_normal(xs.shape)
    return xs.astype(np.float32), labels


def spiral_stream(batch_size: int, T: int = 17, seed: int = 0):
    """The spiral task as an unbounded online stream: `stream(step) ->
    (x_t [B, 2], labels [B])`, one freshly drawn batch of sequences every T
    steps.  Step-keyed, so a restarted worker replays its exact inputs."""
    xs_all, ys_all = spiral_dataset(T=T, seed=0)

    def stream(step: int):
        s, t = divmod(step, T)
        rng = np.random.default_rng(1234 + seed * 100003 + s)
        sel = rng.integers(0, ys_all.shape[0], size=batch_size)
        return xs_all[sel][:, t], ys_all[sel]
    return stream


def spiral_batches(batch_size: int, T: int = 17, n_samples: int = 10_000,
                   seed: int = 0, time_major: bool = True):
    """Infinite batch iterator -> (xs [T,B,2] (or [B,T,2]), labels [B])."""
    xs, labels = spiral_dataset(n_samples, T, seed=seed)
    rng = np.random.default_rng(seed + 1)
    n = xs.shape[0]
    while True:
        idx = rng.integers(0, n, size=batch_size)
        xb, yb = xs[idx], labels[idx]
        if time_major:
            xb = np.swapaxes(xb, 0, 1)
        yield xb, yb
