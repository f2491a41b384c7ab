"""Serving launcher: batched decode with slot-based continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --requests 6 --max-new 12

`--fleet` switches to the multi-tenant online-RTRL fleet instead: a
session queue of independent EGRU streams drained through one
`StreamFleet` (`repro.runtime.fleet`) — sessions join free slots
mid-flight, train for a fixed number of update windows, and leave;
admission is continuous, with zero recompilation.

    PYTHONPATH=src python -m repro.launch.serve --fleet --smoke
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def session_stream(seed: int, B: int, n_in: int, n_out: int):
    """One session's step-keyed stream: Gaussian inputs [B, n_in] and fixed
    labels [B] — replay-exact, so an evicted session resumes bit-for-bit."""
    def stream(step: int):
        rng = np.random.default_rng(seed * 100003 + step)
        x = rng.standard_normal((B, n_in)).astype(np.float32)
        y = (np.arange(B, dtype=np.int32) + seed) % n_out
        return x, y
    return stream


def build_fleet(n: int, B: int, slots: int, update_every: int,
                telemetry=None):
    """The --fleet deployment: EGRU width n at 90% parameter sparsity on
    the dual-compact engine, one StreamFleet of `slots` sessions, each
    streaming batches of B.  Returns (fleet, params0), params0 being the
    parameters every joining session starts from."""
    import jax

    from repro.core import cells, sparse_rtrl as SP
    from repro.core.cells import EGRUConfig
    from repro.core.learner import LearnerSpec, make_learner
    from repro.optim import make_optimizer
    from repro.runtime.fleet import FleetConfig, StreamFleet

    cfg = EGRUConfig(n_hidden=n, n_in=3, n_out=2, kind="gru")
    masks = SP.make_masks(cfg, jax.random.key(7), 0.9)
    learner = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                       backend="compact", col_compact=True))
    opt = make_optimizer("adamw", lr=1e-3)
    params0 = SP.apply_masks(cells.init_params(cfg, jax.random.key(0)), masks)
    fleet = StreamFleet(FleetConfig(slots=slots, update_every=update_every),
                        learner, opt, params0, masks,
                        example=session_stream(0, B, cfg.n_in, cfg.n_out)(0),
                        telemetry=telemetry)
    return fleet, params0


def _fleet_main(args):
    """Drain a queue of online-RTRL sessions through one StreamFleet."""
    from repro.obs import finish_run, telemetry_from_args

    n = 16 if args.smoke else 96
    B = 2 if args.smoke else 8
    n_sessions = min(args.requests, 6) if args.smoke else args.requests
    slots = min(args.slots, 4) if args.smoke else args.slots
    windows = 3 if args.smoke else args.session_windows

    obs = telemetry_from_args(args, mode="fleet", slots=slots,
                              sessions=n_sessions)
    fleet, _ = build_fleet(n, B, slots, args.update_every, telemetry=obs)
    cfg = fleet.learner.cfg
    make_stream = lambda i: session_stream(i, B, cfg.n_in, cfg.n_out)
    queue = [(f"s{i}", make_stream(i)) for i in range(n_sessions)]
    need = {sid: windows for sid, _ in queue}
    done, fleet_windows = 0, 0
    t0 = time.time()
    while done < n_sessions:
        while queue and fleet.free_slots():        # continuous admission
            sid, stream = queue.pop(0)
            fleet.add_session(sid, stream)
        stats = fleet.step_window()
        fleet_windows += 1
        for sid in list(stats):
            need[sid] -= 1
            if need[sid] <= 0:                      # session completes
                fleet.remove(sid)
                done += 1
    dt = time.time() - t0
    rep = fleet.report()
    summary = {"mode": "fleet", "sessions": n_sessions,
               "session_windows": windows, "slots": slots,
               "update_every": args.update_every,
               "fleet_windows": fleet_windows, "wall_s": round(dt, 3),
               "sessions_per_s": round(n_sessions / max(dt, 1e-9), 2),
               "session_carry_bytes": rep["session_carry_bytes"]}
    for p in ("window_ms_p50", "window_ms_p99"):
        if p in rep:
            summary[p] = rep[p]
    return finish_run(obs, "serve fleet (online RTRL)", summary)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--fleet", action="store_true",
                    help="serve a queue of online-RTRL training sessions "
                         "through one StreamFleet instead of decoding")
    ap.add_argument("--update-every", type=int, default=8,
                    help="--fleet: stream steps per update window")
    ap.add_argument("--session-windows", type=int, default=12,
                    help="--fleet: update windows per session")
    from repro.obs import add_obs_args
    add_obs_args(ap)
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.fleet:
        return _fleet_main(args)

    from repro.configs import get_config, smoke_config
    from repro.runtime.serving import Engine, ServeConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.family == "encdec":
        raise SystemExit("whisper serving needs audio prefill; use "
                         "examples/serve_demo.py for the decoder-only flow")

    eng = Engine(cfg, ServeConfig(batch_slots=args.slots,
                                  max_seq=args.max_seq,
                                  temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=rng.integers(4, 9)).tolist()
               for _ in range(args.requests)]
    t0 = time.time()
    outs = eng.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    from repro.obs import finish_run, telemetry_from_args
    obs = telemetry_from_args(args, mode="decode")
    finish_run(obs, f"serve {args.arch} (decode)",
               {"arch": args.arch, "requests": len(prompts),
                "tokens": n_tok, "wall_s": round(dt, 3),
                "tok_per_s": round(n_tok / max(dt, 1e-9), 1),
                "slots": args.slots})
    for i, o in enumerate(outs[:3]):
        print(f"  req{i}: {o}")


if __name__ == "__main__":
    main()
