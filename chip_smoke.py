"""Chip smoke test: the online RTRL path end to end on one TPU chip.

    python chip_smoke.py

One process, no options.  Every phase drives the entry points the launchers
use (`make_learner`, `OnlineTrainer`, `StreamFleet`):

  P  matmul precision: the error of an f32 matmul against float64 for an
     XLA dot at DEFAULT and at HIGHEST precision, a Pallas dot at Mosaic's
     default, and the fused influence kernel as the engine runs it.
  A  the paper's EGRU exactly as published (configs/egru_spiral.py: n=16,
     batch 32, 1 layer), trained online on the spiral stream with
     backend="compact_fused" at 90% parameter sparsity, an update every 8
     steps (what `launch/train.py --arch egru-spiral --online` builds).
  B  the same learner at n=256, n_in=8, batch 4: the widest point recorded
     for the fused kernel (32 row blocks x 168 column blocks per example).
  C  the `launch/serve.py --fleet` deployment at its full size (n=96,
     batch 8, 4 slots): six sessions join and leave over six windows.

Each phase checks its first window's gradients against the dense backend
computed under jax.default_matmul_precision("highest"); A and B also check
that the compiled update chunk holds the Pallas kernel (tpu_custom_call).
Each phase prints one JSON line (device kind, shapes, compile seconds, warm
window ms after block_until_ready, peak device memory, gradient error and
its tolerance).  The last line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

Exits non-zero before any phase where JAX finds no TPU, or where the repo's
sources are not next to this file; exits non-zero after the phases if one
of them failed.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
K_UPDATE = 8          # stream steps per online update (train.py default)
WARM_WINDOWS = 4      # timed windows after the compiling one
U32 = 2.0 ** -24      # f32 unit roundoff


def _fail_early(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def grad_rel_err(g, ref) -> float:
    """max over parameter leaves of max|g - ref| / max|ref|, the denominator
    floored at 1e-6 of the largest reference gradient (a leaf whose
    gradient is ~0 then counts against the whole gradient's scale)."""
    import jax
    import numpy as np
    gl = [np.asarray(x, np.float64) for x in jax.tree.leaves(g)]
    rl = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref)]
    assert len(gl) == len(rl), (len(gl), len(rl))
    floor = 1e-6 * max(float(np.max(np.abs(r))) for r in rl)
    return max(float(np.max(np.abs(a - b)))
               / max(float(np.max(np.abs(b))), floor, 1e-30)
               for a, b in zip(gl, rl))


def grad_tol(n: int) -> tuple[float, str]:
    """Gradient tolerance of an exact engine against the dense oracle.

    Both sides contract in f32 (HIGHEST) and differ only in summation
    order, so the error is bounded by reassociation: about u = 2^-24 per
    add over an n-term contraction, compounded over the k steps of the
    window; the bound below is 4·u·n·k."""
    tol = 4 * U32 * n * K_UPDATE
    return tol, (f"f32 reassociation bound 4*u*n*k (u=2^-24, n={n}, "
                 f"k={K_UPDATE}): both sides at HIGHEST, summation order "
                 f"differs")


def _window(stream, start: int, k: int):
    import numpy as np
    xs, ys = zip(*(stream(start + i) for i in range(k)))
    return np.stack(xs), np.stack(ys)


# ---------------------------------------------------------------------------
# P: matmul precision
# ---------------------------------------------------------------------------

def phase_precision(interpret: bool | None = None) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from repro.kernels import compact_fused as CF

    rng = np.random.default_rng(0)
    M, K, N = 256, 256, 512
    a = rng.standard_normal((M, K)).astype(np.float32)
    b = rng.standard_normal((K, N)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)

    def err(x):
        d = np.asarray(x, np.float64) - exact
        return float(np.max(np.abs(d)) / np.max(np.abs(exact)))

    def xla_dot(x, y):
        return jnp.matmul(x, y)

    xla_default = err(jax.jit(xla_dot)(a, b))
    with jax.default_matmul_precision("highest"):
        xla_highest = err(jax.jit(xla_dot)(a, b))

    def dot_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = jax.lax.dot(x_ref[...], y_ref[...],
                                 preferred_element_type=jnp.float32)

    mosaic = pl.pallas_call(
        dot_kernel, out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=bool(interpret))
    mosaic_default = err(jax.jit(mosaic)(a, b))

    fused = functools.partial(CF.fused_update_pallas, interpret=interpret)
    out = jax.jit(fused)(
        jnp.asarray(a)[None], jnp.asarray(b)[None],
        jnp.zeros((1, K, N), jnp.float32), jnp.ones((1, K), jnp.float32),
        jnp.array([K], jnp.int32), jnp.array([K], jnp.int32))
    fused_kernel = err(out[0])
    rec = {"phase": "P", "what": "rel err of a 256x256x512 f32 matmul vs "
           "float64", "xla_default": xla_default, "xla_highest": xla_highest,
           "mosaic_default": mosaic_default, "fused_kernel": fused_kernel,
           "fused_kernel_tol": 1e-5}
    rec["ok"] = fused_kernel < 1e-5
    return rec


# ---------------------------------------------------------------------------
# A / B: one online stream through OnlineTrainer
# ---------------------------------------------------------------------------

def phase_online(name: str, cfg, stream, *, sparsity: float = 0.9,
                 seed: int = 0, interpret: bool | None = None,
                 require_kernel: bool = True) -> dict:
    """Online compact_fused training of a 1-layer EGRU stack through
    `OnlineTrainer`, set up as `launch/train.py --online` sets it up."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cells, stacked_rtrl as ST
    from repro.core.learner import LearnerSpec, make_learner
    from repro.optim import make_optimizer
    from repro.optim.optimizers import masked
    from repro.runtime.online import (OnlineTrainer, OnlineTrainerConfig,
                                      stream_grads)

    k = K_UPDATE
    n = cfg.layer_cfg(0).n_hidden
    key = jax.random.key(seed)
    masks = ST.make_stacked_masks(cfg, jax.random.fold_in(key, 1), sparsity)
    params = ST.apply_stacked_masks(cells.init_stacked_params(cfg, key), masks)
    opt = masked(make_optimizer("adamw", lr=cfg.lr),
                 {"layers": masks, "out": None})
    spec = LearnerSpec(engine="stacked", cfg=cfg, backend="compact_fused",
                       col_compact=True, interpret=interpret)
    learner = make_learner(spec)
    trainer = OnlineTrainer(
        OnlineTrainerConfig(total_steps=k, update_every=k, log_every=1,
                            seed=seed),
        learner, opt, params, masks, stream)
    xs, ys = _window(stream, 0, k)
    xs, ys = jnp.asarray(xs), jnp.asarray(ys)
    rec = {"phase": name, "n": n, "n_in": cfg.layer_cfg(0).n_in,
           "batch": int(xs.shape[1]), "layers": cfg.n_layers,
           "update_every": k, "sparsity": sparsity,
           "backend": spec.backend,
           "carry_shape": list(trainer.carry["vals"].shape),
           "carry_dtype": str(trainer.carry["vals"].dtype)}

    # first window's gradients: the engine vs the dense oracle at HIGHEST
    g = jax.jit(lambda c, x, y: stream_grads(learner, c, x, y)[2])(
        trainer.carry, xs, ys)
    ref_learner = make_learner(dataclasses.replace(
        spec, backend="dense", col_compact=None))
    ref_carry = ref_learner.init(params, masks, (xs[0], ys[0]), t_total=k)
    with jax.default_matmul_precision("highest"):
        r = jax.jit(lambda c, x, y: stream_grads(ref_learner, c, x, y)[2])(
            ref_carry, xs, ys)
    rec["grad_rel_err"] = grad_rel_err(ST.apply_stacked_masks(g, masks),
                                       ST.apply_stacked_masks(r, masks))
    rec["grad_tol"], rec["grad_tol_reason"] = grad_tol(n)
    del ref_carry, r

    # the update chunk the trainer runs, compiled ahead: kernel present?
    t0 = time.perf_counter()
    compiled = trainer._chunk.lower(trainer.carry, trainer.opt_state,
                                    xs, ys, jnp.int32(0)).compile()
    rec["compile_s"] = time.perf_counter() - t0
    rec["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["chunk_temp_bytes"] = int(ma.temp_size_in_bytes)
        rec["chunk_argument_bytes"] = int(ma.argument_size_in_bytes)

    # one window (compiles through the trainer's own jit), then warm ones
    hist = trainer.obs.registry.histogram("window_ms")
    trainer.run()
    rec["first_window_ms"] = hist.sum
    s0, c0 = hist.sum, hist.count
    trainer.cfg.total_steps += WARM_WINDOWS * k
    out = trainer.run()
    rec["warm_window_ms_mean"] = (hist.sum - s0) / (hist.count - c0)
    rec["warm_window_ms_min"] = hist.min
    rec["warm_windows"] = hist.count - c0
    losses = [m["loss"] for m in out["metrics"] if "loss" in m]
    rec["loss_first"], rec["loss_last"] = losses[0], losses[-1]
    rec["overflow"] = max(m.get("overflow", 0.0) for m in out["metrics"])
    rec["peak_bytes_in_use"] = _peak_bytes()
    rec["ok"] = bool(np.all(np.isfinite(losses)) and rec["overflow"] == 0
                     and rec["grad_rel_err"] <= rec["grad_tol"]
                     and (rec["tpu_custom_call"] or not require_kernel))
    return rec


# ---------------------------------------------------------------------------
# C: the fleet of serve.py --fleet
# ---------------------------------------------------------------------------

def phase_fleet(n: int = 96, B: int = 8, slots: int = 4, sessions: int = 6,
                session_windows: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sparse_rtrl as SP
    from repro.core.learner import LearnerSpec, make_learner
    from repro.launch.serve import build_fleet, session_stream
    from repro.runtime.online import stream_grads

    k = K_UPDATE
    fleet, params0 = build_fleet(n, B, slots, k)
    learner, masks = fleet.learner, fleet.masks
    cfg = learner.cfg
    queue = [(f"s{i}", session_stream(i, B, cfg.n_in, cfg.n_out))
             for i in range(sessions)]
    rec = {"phase": "C", "n": n, "n_in": cfg.n_in, "batch": B,
           "slots": slots, "sessions": sessions,
           "session_windows": session_windows, "update_every": k,
           "backend": learner.spec.backend,
           "session_carry_bytes": fleet.session_carry_bytes}

    # first window of the first `slots` sessions: the vmapped engine (what
    # fleet_update_chunk runs) vs the dense oracle per session.  Computed
    # before the first step_window, which donates the fleet's carry.
    first = queue[:slots]
    for sid, stream in first:
        fleet.add_session(sid, stream)
    wins = [_window(stream, 0, k) for _, stream in first]
    xs = jnp.asarray(np.stack([w[0] for w in wins]))
    ys = jnp.asarray(np.stack([w[1] for w in wins]))
    g = jax.jit(jax.vmap(
        lambda c, x, y: stream_grads(learner, c, x, y)[2]))(fleet.carry,
                                                           xs, ys)
    ref_learner = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                           backend="dense"))
    ref_carry = ref_learner.init(params0, masks, (xs[0, 0], ys[0, 0]),
                                 t_total=k)
    errs = []
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(lambda c, x, y: stream_grads(ref_learner, c, x, y)[2])
        for s in range(slots):
            r = ref_fn(ref_carry, xs[s], ys[s])
            gs = jax.tree.map(lambda v: v[s], g)
            errs.append(grad_rel_err(SP.apply_masks(gs, masks),
                                     SP.apply_masks(r, masks)))
    rec["grad_rel_err"] = max(errs)
    rec["grad_tol"], rec["grad_tol_reason"] = grad_tol(n)
    del g, ref_carry

    # the fleet's update chunk, compiled ahead (lowering donates nothing)
    t0 = time.perf_counter()
    fleet._chunk.lower(fleet.carry, fleet.opt_state, xs, ys,
                       jnp.zeros((slots,), jnp.int32),
                       jnp.ones((slots,), bool)).compile()
    rec["compile_s"] = time.perf_counter() - t0

    # the serve.py drain: admit into free slots, step, leave when done
    queue = queue[slots:]
    left = {sid: session_windows for sid, _ in first}
    window_ms, losses, joined, done = [], [], slots, 0
    while done < sessions:
        while queue and fleet.free_slots():
            sid, stream = queue.pop(0)
            fleet.add_session(sid, stream)
            left[sid] = session_windows
            joined += 1
        t0 = time.perf_counter()
        stats = fleet.step_window()            # ends in the packed readback
        window_ms.append((time.perf_counter() - t0) * 1e3)
        for sid, st in stats.items():
            losses.append(st["loss"])
            left[sid] -= 1
            if left[sid] == 0:
                fleet.remove(sid)
                done += 1
    rec["windows"] = len(window_ms)
    rec["first_window_ms"] = window_ms[0]
    rec["warm_window_ms_mean"] = float(np.mean(window_ms[1:]))
    rec["warm_window_ms_min"] = float(np.min(window_ms[1:]))
    rec["joined"], rec["left"] = joined, done
    rec["peak_bytes_in_use"] = _peak_bytes()
    rec["ok"] = bool(np.all(np.isfinite(losses))
                     and rec["grad_rel_err"] <= rec["grad_tol"]
                     and joined == sessions and done == sessions)
    return rec


# ---------------------------------------------------------------------------

def phases():
    """(name, thunk) for every phase, in order."""
    from repro.configs import egru_spiral
    from repro.core.cells import stacked_config
    from repro.data.spiral import spiral_stream
    from repro.launch.serve import session_stream

    paper = egru_spiral.stacked(1)
    wide_layer = dataclasses.replace(egru_spiral.CONFIG, n_hidden=256,
                                     n_in=8, batch_size=4)
    wide = stacked_config(wide_layer, 1)
    return [
        ("P", phase_precision),
        ("A", lambda: phase_online(
            "A", paper, spiral_stream(paper.layer_cfg(0).batch_size,
                                      T=paper.layer_cfg(0).seq_len))),
        ("B", lambda: phase_online(
            "B", wide, session_stream(0, 4, 8, wide_layer.n_out))),
        ("C", phase_fleet),
    ]


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail_early(f"no TPU: JAX's first device is {dev.platform} "
                    f"({dev.device_kind}); this smoke test runs only on a "
                    f"TPU chip")
    if not (ROOT / "src" / "repro").is_dir():
        _fail_early(f"the repo's sources (src/repro) are not next to "
                    f"{Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _emit({"device": device, "jax": jax.__version__,
           "compile_cache": cache_dir})

    failed = []
    for name, run in phases():
        t0 = time.perf_counter()
        try:
            rec = run()
        except Exception:                      # report, run the next phase
            traceback.print_exc()
            rec = {"phase": name, "ok": False, "error": "exception (stderr)"}
        rec["device_kind"] = dev.device_kind
        rec["phase_s"] = time.perf_counter() - t0
        _emit(rec)
        if not rec["ok"]:
            failed.append(name)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
