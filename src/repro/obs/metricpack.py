"""In-jit metrics packing: every runtime scalar, one readback.

The paper's efficiency claim rides on runtime-varying quantities — the
measured activity sparsity omega-hat and the live parameter density drive
the w~ b~^2 n^2 p cost — so a credible run must MEASURE them, every
window, without perturbing the computation or adding host syncs.
`MetricPack` generalizes the guard's one-packed-buffer trick
(`runtime/guard.py::_pack_verdict`) into a declarative registry of
in-graph scalars:

- each field is ``(name, fn)`` where ``fn(env) -> scalar`` reads the
  update chunk's environment (window loss, gradient tree, per-step stats
  traces, the post-update carry, guard clip factor / health bits);
- ``pack(env)`` stacks every field into ONE ``[F]`` float32 vector that
  the chunk returns alongside its metrics, so all F scalars cost a single
  device->host readback per window;
- ``unpack(vec)`` maps the fetched vector back to ``{name: float}``.

Fields are *pure observers*: they only reduce values the chunk already
computed (scalar reductions do not change how XLA compiles the chunk's
own dataflow — the instrumented chunk's carry/opt-state outputs are
BITWISE identical to the uninstrumented ones, pinned for the solo and
vmapped-fleet chunks in tests/test_obs.py).  A field whose source is
absent for this engine (no compact `idx` buffer, no rewirable column
mask) packs NaN — `unpack` surfaces it as NaN and the JSONL writer drops
it, so one pack definition serves every engine.

This module deliberately imports NOTHING from `repro.runtime` (the
runtime imports it), and every probe of the env is a host-side dict/key
check at trace time — the packed program contains only the reductions.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

Tree = Any

_NAN = float("nan")


def global_norm(tree) -> jax.Array:
    """sqrt(sum of squares) over every leaf, f32 accumulation — identical
    formulation to the guard's clip norm, so the packed `grad_norm` equals
    the norm the clip decision used."""
    leaves = [jnp.sum(jnp.square(jnp.asarray(x).astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(leaves))


def _scalar(v) -> jax.Array:
    return jnp.asarray(v, jnp.float32).reshape(())


def _stat_mean(key):
    def fn(env):
        stats = env.get("stats") or {}
        if key not in stats:
            return _scalar(_NAN)
        return _scalar(jnp.mean(jnp.asarray(stats[key], jnp.float32)))
    return fn


def _stat_sum(key):
    def fn(env):
        stats = env.get("stats") or {}
        if key not in stats:
            return _scalar(_NAN)
        return _scalar(jnp.sum(jnp.asarray(stats[key], jnp.float32)))
    return fn


def _f_loss(env):
    return _scalar(env["loss"])


def _f_grad_norm(env):
    if "grad_norm" in env:                  # guard chunk already computed it
        return _scalar(env["grad_norm"])
    grads = env.get("grads")
    if grads is None:
        return _scalar(_NAN)
    return _scalar(global_norm(grads))


def _f_overflow(env):
    stats = env.get("stats") or {}
    if "overflow" not in stats:
        return _scalar(_NAN)                # engine doesn't track capacity
    # max, not mean: any nonzero step means the window's gradients are no
    # longer exact — same convention as the chunk metrics
    return _scalar(jnp.max(jnp.asarray(stats["overflow"], jnp.float32)))


def _f_live_col_frac(env):
    """Live fraction of the influence column axis.  Dynamic (in-graph) for
    rewirable carries — the mask state rides in carry['rw'] — NaN
    otherwise (the static layout is a config constant, reported host-side
    by `OnlineTrainer.carry_nbytes`)."""
    carry = env.get("carry")
    rw = carry.get("rw") if isinstance(carry, dict) else None
    if not isinstance(rw, dict):
        return _scalar(_NAN)
    if "cl" in rw:
        live = rw["cl"]["live"]
    elif "colm" in rw:
        live = rw["colm"]
    elif "colms" in rw:
        live = rw["colms"][-1]
    else:
        return _scalar(_NAN)
    return _scalar(jnp.mean(jnp.asarray(live, jnp.float32)))


def _kb_counts(carry):
    """Per-(buffer, example) live-row counts of a compact influence carry,
    or None off the compact backends — the in-graph twin of
    `OnlineTrainer.row_stats`."""
    if not isinstance(carry, dict):
        return None
    bufs = []
    for holder in (carry, carry.get("state") or {}):
        if not isinstance(holder, dict):
            continue
        idx = holder.get("idx")
        if idx is None:
            continue
        bufs += list(idx) if isinstance(idx, tuple) else [idx]
    if not bufs:
        return None
    return jnp.concatenate(
        [jnp.sum((jnp.asarray(b) >= 0).astype(jnp.float32), axis=-1).ravel()
         for b in bufs])


def _f_kb(reduce):
    def fn(env):
        kb = _kb_counts(env.get("carry"))
        if kb is None:
            return _scalar(_NAN)
        return _scalar({"min": jnp.min, "mean": jnp.mean,
                        "max": jnp.max}[reduce](kb))
    return fn


def _f_env(key, default):
    def fn(env):
        return _scalar(env.get(key, default))
    return fn


# the standard catalog, in packed order (README documents it)
DEFAULT_FIELDS = (
    ("loss", _f_loss),                       # window loss (sum of 1/t_total-scaled steps)
    ("grad_norm", _f_grad_norm),             # global gradient norm, pre-clip-scale
    ("act_sparsity", _stat_mean("alpha")),   # omega-hat: mean forward activity sparsity
    ("bwd_sparsity", _stat_mean("beta")),    # beta-hat: mean backward (pseudo-deriv) sparsity
    ("overflow", _f_overflow),               # compact-capacity overflow (max over window)
    ("live_col_frac", _f_live_col_frac),     # live influence columns / total (rewirable)
    ("kb_min", _f_kb("min")),                # ragged per-example active rows K_b
    ("kb_mean", _f_kb("mean")),
    ("kb_max", _f_kb("max")),
    ("clip_factor", _f_env("clip_factor", 1.0)),  # guard norm-clip scale (1 = untouched)
    ("health", _f_env("health", 0.0)),       # guard finiteness bitmask (0 = healthy)
    ("resets", _stat_sum("resets")),         # document starts in the window (token streams)
    ("tokens", _stat_sum("tokens")),         # tokens the window consumed (token streams)
)


class MetricPack:
    """An ordered, declarative set of in-graph scalar fields."""

    def __init__(self, fields=DEFAULT_FIELDS):
        self.fields = tuple(fields)
        self.names = tuple(n for n, _ in self.fields)
        if len(set(self.names)) != len(self.names):
            raise ValueError(f"duplicate metric names: {self.names}")

    @classmethod
    def default(cls, exclude: tuple = ()) -> "MetricPack":
        return cls(tuple(f for f in DEFAULT_FIELDS if f[0] not in exclude))

    def pack(self, env: dict) -> jax.Array:
        """[F] float32 — call INSIDE the jitted chunk.  env keys (all
        optional except 'loss'): loss, grads, stats, carry, grad_norm,
        clip_factor, health."""
        with jax.named_scope("telemetry"):
            return jnp.stack([fn(env) for _, fn in self.fields])

    def unpack(self, vec) -> dict:
        """Fetched [F] (or [..., F]) vector -> {name: float} (leading axes
        -> lists).  The single host-side decode of the packed readback."""
        import numpy as np
        a = np.asarray(jax.device_get(vec), dtype=np.float32)
        if a.shape[-1] != len(self.names):
            raise ValueError(f"packed vector has {a.shape[-1]} fields, "
                             f"pack defines {len(self.names)}")
        if a.ndim == 1:
            return {n: float(a[i]) for i, n in enumerate(self.names)}
        return {n: a[..., i] for i, n in enumerate(self.names)}
