"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 20 --ckpt-dir /tmp/ck [--fail-at 7] [--resume]

--smoke uses the reduced same-family config (CPU-runnable); omit it on a
real pod to train the full config on the production mesh.  Failure
injection + auto-restart demonstrate the fault-tolerance path end-to-end.

EGRU / exact-RTRL path (the paper's own experiment, stacked to depth L):

    PYTHONPATH=src python -m repro.launch.train --arch egru-spiral \
        --layers 2 --steps 200 [--rtrl-backend compact] [--sparsity 0.8]

trains an L-layer EGRU stack on the spiral task with exact block-structured
stacked RTRL (repro.core.stacked_rtrl) through the same fault-tolerant
Trainer / restart supervisor as the LM families.

ONLINE path (the streaming Learner API — what RTRL buys over BPTT):

    PYTHONPATH=src python -m repro.launch.train --arch egru-spiral \
        --online --update-every 8 --steps 100 [--rtrl-backend compact]

consumes the spiral task as an unbounded stream and applies an optimizer
update every k steps MID-SEQUENCE (repro.runtime.online.OnlineTrainer):
memory is O(1) in stream length, checkpoints include the learner carry so
restarts resume mid-stream, and --steps counts optimizer updates.

Online token-LM path (the cell zoo — repro.cells — behind the same stream):

    PYTHONPATH=src python -m repro.launch.train --arch rglru-lm --online \
        --smoke --steps 10 [--vocab 64 --width 64]

trains a next-token head online, one token per stream step, with the
engine matched to the cell: egru-lm -> 'sparse' (dense-Jacobian influence),
rglru-lm -> 'diag_exact' (exact O(n·p) diagonal traces), snn-lm -> 'eprop'
(spiking eligibility traces).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, smoke_config
from repro.data.pipeline import ShardedHostLoader
from repro.data.tokens import synthetic_token_batches
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.models.module import materialize, tree_shardings
from repro.obs import add_obs_args, finish_run, telemetry_from_args
from repro.runtime.trainer import Trainer, TrainerConfig, run_with_restart
from repro.sharding import make_rules


def _loss_fields(metrics: list) -> dict:
    """first/final loss (+ sparsity telemetry) from the trainer's metric
    records — quarantined windows log without a loss entry, so summarize
    over the records that have one."""
    with_loss = [m for m in metrics if "loss" in m]
    if not with_loss:
        return {}
    first, last = with_loss[0], with_loss[-1]
    out = {"first_loss": first["loss"], "final_loss": last["loss"]}
    if "alpha" in last:
        out["act_sparsity"] = last["alpha"]
    if "beta" in last:
        out["bwd_sparsity"] = last["beta"]
    return out


def train_egru(args) -> dict:
    """Stacked-EGRU exact-RTRL training on the spiral task, end to end:
    block-structured influence engine + masked optimizer + the same
    checkpoint/restart Trainer the LM families use."""
    from repro.configs import egru_spiral
    from repro.core import cells, stacked_rtrl as ST
    from repro.data.spiral import spiral_dataset
    from repro.optim import make_optimizer
    from repro.optim.optimizers import masked

    cfg = egru_spiral.stacked(args.layers)
    backend = args.rtrl_backend
    rewiring = args.rewire != "off"
    if rewiring and not args.online:
        raise SystemExit("--rewire needs --online (events fire at online "
                         "update boundaries)")
    if rewiring and args.sparsity <= 0.0:
        raise SystemExit("--rewire needs --sparsity > 0 (there is no mask "
                         "to evolve at density 1)")
    if rewiring and backend == "compact_fused":
        raise SystemExit("--rewire is not supported with the compact_fused "
                         "backend (its gate-segment table is compiled from "
                         "the init-time masks) — use --rtrl-backend compact")
    # --seed threads EVERYTHING: params, mask draws (via the documented
    # make_masks key convention), the stream shuffle base, and the per-event
    # rewire keys — one seed reproduces a run end-to-end, rewires included
    base_key = jax.random.key(args.seed)
    masks = None
    if args.sparsity > 0.0:
        masks = ST.make_stacked_masks(cfg, jax.random.fold_in(base_key, 1),
                                      args.sparsity)
    # resolve the auto rule ONCE and pass the explicit bool to the engine,
    # so the report below can never disagree with what the engine runs
    col_flag = {"auto": None, "on": True, "off": False}[args.col_compact]
    if backend == "compact_fused":
        if col_flag is False:
            raise SystemExit("--col-compact off conflicts with "
                             "--rtrl-backend compact_fused (the fused "
                             "engine always carries column-compact)")
        col_compact = True
    else:
        col_compact = (masks is not None and backend != "dense"
                       if col_flag is None else col_flag)
    if masks is not None and backend != "dense":
        slayout = ST.stacked_layout(cfg)
        live = int(np.asarray(ST.stacked_col_mask(slayout, masks)).sum())
        print(f"influence columns: {live}/{slayout.P_total} live "
              f"(omega~={ST.stacked_omega_tilde(masks):.3f}); "
              f"col-compact carry {'ON' if col_compact else 'OFF'}")
    opt = make_optimizer("adamw", lr=cfg.lr)
    if masks is not None:
        from repro.optim.optimizers import masked_dynamic
        opt_mask = {"layers": masks, "out": None}
        # rewiring swaps masks at runtime -> the mask must live in the
        # optimizer STATE, not a jit-baked closure constant
        opt = masked_dynamic(opt, opt_mask) if rewiring \
            else masked(opt, opt_mask)

    if args.online:
        return train_egru_online(args, cfg, masks, opt, backend, col_compact)

    @jax.jit
    def step_fn(params, opt_state, batch, step):
        xs, ys = batch
        loss, grads, stats = ST.stacked_rtrl_loss_and_grads(
            cfg, params, xs, ys, masks, backend=backend,
            capacity=args.capacity, col_compact=col_compact,
            influence_dtype=args.influence_dtype)
        params, opt_state = opt.update(grads, opt_state, params, step)
        metrics = {"loss": loss, "alpha": stats["alpha"].mean(),
                   "beta": stats["beta"].mean()}
        if "overflow" in stats:
            metrics["overflow"] = stats["overflow"].max()
        return params, opt_state, metrics

    xs_all, ys_all = spiral_dataset(T=cfg.seq_len, seed=0)

    def data_at(step):    # step-keyed: replay-exact across restarts
        rng = np.random.default_rng(1234 + step)
        sel = rng.integers(0, ys_all.shape[0], size=cfg.batch_size)
        return (jnp.asarray(np.swapaxes(xs_all[sel], 0, 1)),
                jnp.asarray(ys_all[sel]))

    def make_trainer(attempt=0):
        params = cells.init_stacked_params(cfg, jax.random.key(args.seed))
        if masks is not None:
            params = ST.apply_stacked_masks(params, masks)
        opt_state = jax.jit(opt.init)(params)
        tcfg = TrainerConfig(total_steps=args.steps,
                             ckpt_every=args.ckpt_every,
                             ckpt_dir=args.ckpt_dir,
                             fail_at_step=args.fail_at if attempt == 0 else -1,
                             metrics_path=args.metrics)

        def wrapped(params, opt_state, batch, step):
            return step_fn(params, opt_state, batch, jnp.int32(step))

        return Trainer(tcfg, wrapped, params, opt_state, data_at)

    out = run_with_restart(make_trainer)
    obs = telemetry_from_args(args, arch="egru-spiral", mode="offline")
    finish_run(obs, "train egru-spiral (offline RTRL)",
               {"arch": "egru-spiral", "mode": "offline",
                "layers": args.layers, "backend": backend,
                "final_step": out["final_step"],
                "restarts": out["restarts"],
                "stragglers": out["stragglers"],
                **_loss_fields(out["metrics"])})
    return out


def train_egru_online(args, cfg, masks, opt, backend, col_compact) -> dict:
    """True ONLINE training on the spiral stream: optimizer updates every
    `--update-every` stream steps, mid-sequence, through the streaming
    Learner API — memory O(1) in stream length, learner carry checkpointed
    so restarts resume mid-stream.  `--steps` counts optimizer updates."""
    from repro.core import cells, stacked_rtrl as ST
    from repro.core.learner import LearnerSpec, make_learner
    from repro.data.spiral import spiral_stream
    from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig
    from repro.sparsity import RewireSchedule

    from repro.runtime.guard import FaultPlan, GuardConfig

    updates = min(args.steps, 12) if args.smoke else args.steps
    k = args.update_every
    rewiring = args.rewire != "off"
    guard_cfg = None
    if args.guard:
        guard_cfg = GuardConfig(ring=args.guard_ring,
                                policy=args.guard_policy)
    spec = LearnerSpec(engine="stacked", cfg=cfg, backend=backend,
                       capacity=args.capacity, col_compact=col_compact,
                       rewirable=rewiring,
                       influence_dtype=args.influence_dtype)
    learner = make_learner(spec)
    schedule = None
    if rewiring:
        n_events = max(1, updates // args.rewire_every)
        schedule = RewireSchedule(method=args.rewire,
                                  every_k=args.rewire_every,
                                  frac=args.rewire_frac, t_end=n_events)

    stream = spiral_stream(cfg.batch_size, T=cfg.seq_len, seed=args.seed)
    obs = telemetry_from_args(args, arch="egru-spiral", mode="online",
                              backend=backend, col_compact=col_compact)

    def make_trainer(attempt=0):
        params = cells.init_stacked_params(cfg, jax.random.key(args.seed))
        if masks is not None:
            params = ST.apply_stacked_masks(params, masks)
        ocfg = OnlineTrainerConfig(
            total_steps=updates * k, update_every=k,
            ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
            fail_at_update=args.fail_at if attempt == 0 else -1,
            metrics_path=args.metrics, seed=args.seed)
        plan = None
        if args.inject_nan_at >= 0 or args.inject_corrupt_at >= 0:
            # NaN inputs stay armed across restarts (a data fault lives in
            # the stream); carry corruption is one-shot like --fail-at
            plan = FaultPlan(
                nan_input_at=args.inject_nan_at,
                nan_input_len=args.inject_nan_len,
                corrupt_carry_at_update=(args.inject_corrupt_at
                                         if attempt == 0 else -1))
        return OnlineTrainer(ocfg, learner, opt, params, masks, stream,
                             rewire_schedule=schedule, guard=guard_cfg,
                             fault_plan=plan, telemetry=obs)

    out = run_with_restart(make_trainer)
    summary = {"arch": "egru-spiral", "mode": "online",
               "layers": args.layers, "backend": backend,
               "update_every": k, "updates": out["updates"],
               "final_step": out["final_step"],
               "restarts": out["restarts"],
               "stragglers": out["stragglers"],
               "carry_bytes": out["carry_bytes"],
               "carry_live_bytes": out["carry_live_bytes"],
               **_loss_fields(out["metrics"])}
    if rewiring:
        summary["rewire"] = args.rewire
        summary["rewire_events"] = out["rewire_events"]
    if "guard" in out:
        g = out["guard"]
        summary["guard"] = {"faults": g["faults"],
                            "rollbacks": g["rollbacks"],
                            "recovered": len(g["recoveries"]),
                            "quarantined": len(g["quarantined"])}
    finish_run(obs, "train egru-spiral (online RTRL)", summary)
    return out


LM_ARCHS = {"egru-lm": "sparse", "rglru-lm": "diag_exact", "snn-lm": "eprop"}


def train_lm_online(args) -> dict:
    """The first ONLINE token-LM workload: a single-token stream
    (repro.data.tokens.token_lm_stream) driven through OnlineTrainer with a
    cell-zoo engine per --arch —

        egru-lm    engine='sparse'      (dense-Jacobian influence, EGRU)
        rglru-lm   engine='diag_exact'  (exact O(n·p) diagonal traces)
        snn-lm     engine='eprop'       (approximate spiking eligibility)

    The next-token head IS the learner's readout (n_out = vocab), trained
    online through the same mid-sequence update / checkpoint / restart
    machinery as the spiral task.  --steps counts optimizer updates."""
    from repro.core import sparse_rtrl as SP
    from repro.core.cells import EGRUConfig
    from repro.core.learner import LearnerSpec, make_learner
    from repro.cells.rglru import RGLRUCellConfig
    from repro.cells.rglru import make_masks as rglru_masks
    from repro.cells.snn import SNNConfig
    from repro.data.tokens import token_lm_stream
    from repro.optim import make_optimizer
    from repro.optim.optimizers import masked
    from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig

    if not args.online:
        raise SystemExit(f"--arch {args.arch} is an online streaming "
                         f"workload — pass --online (--steps counts "
                         f"optimizer updates)")
    engine = LM_ARCHS[args.arch]
    vocab = 16 if args.smoke else args.vocab
    width = min(args.width, 32) if args.smoke else args.width
    updates = min(args.steps, 10) if args.smoke else args.steps
    k = args.update_every
    base_key = jax.random.key(args.seed)

    masks = None
    if engine == "sparse":
        cfg = EGRUConfig(n_hidden=width, n_in=vocab, n_out=vocab, kind="gru")
        if args.sparsity > 0.0:
            masks = SP.make_masks(cfg, jax.random.fold_in(base_key, 1),
                                  args.sparsity)
        spec = LearnerSpec(engine="sparse", cfg=cfg,
                           backend=args.rtrl_backend,
                           capacity=args.capacity)
    elif engine == "diag_exact":
        cfg = RGLRUCellConfig(n=width, n_in=vocab, n_out=vocab)
        if args.sparsity > 0.0:
            masks = rglru_masks(cfg, jax.random.fold_in(base_key, 1),
                                args.sparsity)
        spec = LearnerSpec(engine="diag_exact", cfg=cfg)
    else:
        if args.sparsity > 0.0:
            raise SystemExit("--sparsity is not wired for snn-lm (no "
                             "parameter-mask convention for the spiking "
                             "cell yet)")
        cfg = SNNConfig(n=width, n_in=vocab, n_out=vocab)
        spec = LearnerSpec(engine="eprop", cfg=cfg)
    learner = make_learner(spec)

    opt = make_optimizer("adamw", lr=args.lr)
    if masks is not None:
        opt_mask = dict(masks)
        opt_mask.setdefault("out", None)
        opt = masked(opt, opt_mask)

    stream = token_lm_stream(args.batch, vocab, seq=args.seq,
                             seed=1234 + args.seed)
    obs = telemetry_from_args(args, arch=args.arch, engine=engine,
                              vocab=vocab, width=width)

    def make_trainer(attempt=0):
        from repro.cells import resolve_cell
        cell = resolve_cell(cfg)
        params = cell.init_params(jax.random.fold_in(base_key, 0))
        if masks is not None:
            params = SP.apply_masks(params, masks) if engine == "sparse" \
                else {kk: (v * masks[kk] if kk in masks else v)
                      for kk, v in params.items()}
        ocfg = OnlineTrainerConfig(
            total_steps=updates * k, update_every=k,
            ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
            fail_at_update=args.fail_at if attempt == 0 else -1,
            metrics_path=args.metrics, seed=args.seed)
        return OnlineTrainer(ocfg, learner, opt, params, masks, stream,
                             telemetry=obs)

    out = run_with_restart(make_trainer)
    finish_run(obs, f"train {args.arch} (online token LM)",
               {"arch": args.arch, "mode": "online", "engine": engine,
                "vocab": vocab, "width": width, "update_every": k,
                "updates": out["updates"],
                "final_step": out["final_step"],
                "restarts": out["restarts"],
                "stragglers": out["stragglers"],
                "carry_bytes": out["carry_bytes"],
                **_loss_fields(out["metrics"])})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=-1)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--layers", type=int, default=1,
                    help="EGRU stack depth (egru-spiral only)")
    ap.add_argument("--rtrl-backend", default="dense",
                    choices=["dense", "pallas", "compact", "compact_fused"])
    ap.add_argument("--capacity", type=float, default=1.0,
                    help="compact-backend row capacity fraction")
    ap.add_argument("--influence-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="influence-carry dtype (compact backends): "
                         "bfloat16 halves the carry bytes, contractions "
                         "still accumulate in f32")
    ap.add_argument("--sparsity", type=float, default=0.0,
                    help="fixed parameter sparsity (egru-spiral only)")
    ap.add_argument("--online", action="store_true",
                    help="streaming Learner-API training: optimizer updates "
                         "every --update-every stream steps, mid-sequence "
                         "(egru-spiral only; --steps counts updates)")
    ap.add_argument("--update-every", type=int, default=8,
                    help="online mode: stream steps between optimizer "
                         "updates")
    ap.add_argument("--col-compact", choices=["auto", "on", "off"],
                    default="auto",
                    help="carry the influence parameter axis column-compact "
                         "(auto: on whenever --sparsity > 0 and the backend "
                         "is not 'dense')")
    ap.add_argument("--rewire", choices=["off", "set", "rigl"],
                    default="off",
                    help="dynamic sparsity: prune-and-regrow the parameter "
                         "masks at online update boundaries with EXACT "
                         "influence-carry migration (egru-spiral --online "
                         "only; 'set' = random regrowth, 'rigl' = "
                         "gradient-magnitude regrowth)")
    ap.add_argument("--rewire-every", type=int, default=50,
                    help="optimizer updates between rewire events")
    ap.add_argument("--rewire-frac", type=float, default=0.3,
                    help="initial rewired fraction of live weights per "
                         "tensor (cosine-decayed to 0 over the run)")
    ap.add_argument("--guard", action="store_true",
                    help="online mode: enable the StreamGuard — fused "
                         "carry/grad/loss health checks every update, "
                         "rollback-and-replay from a known-good snapshot "
                         "ring under an escalating degradation policy "
                         "(repro.runtime.guard)")
    ap.add_argument("--guard-ring", type=int, default=4,
                    help="known-good snapshots retained for rollback")
    ap.add_argument("--guard-policy", default="full",
                    help="escalation ladder: a preset (full | strict | "
                         "replay-only) or a comma-separated list from "
                         "{replay, clip, skip_update, quarantine}")
    ap.add_argument("--inject-nan-at", type=int, default=-1,
                    help="fault injection (online): stream steps "
                         "[k, k+len) read NaN inputs — persists across "
                         "replay, exercising quarantine")
    ap.add_argument("--inject-nan-len", type=int, default=1,
                    help="length of the injected NaN input window")
    ap.add_argument("--inject-corrupt-at", type=int, default=-1,
                    help="fault injection (online): poison one influence "
                         "element in place after this update commits — "
                         "transient, healed by rollback+replay")
    ap.add_argument("--vocab", type=int, default=64,
                    help="token vocabulary (the *-lm online archs; --smoke "
                         "forces 16)")
    ap.add_argument("--width", type=int, default=64,
                    help="recurrent state width for the *-lm online archs")
    ap.add_argument("--lr", type=float, default=3e-3,
                    help="learning rate for the *-lm online archs")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed threaded through param init, mask "
                         "draws, the data stream, and rewire event keys — "
                         "one value reproduces a run end-to-end")
    add_obs_args(ap)
    args = ap.parse_args()
    setup_compile_cache()

    if args.arch in ("egru-spiral", "egru_spiral"):
        train_egru(args)
        return
    if args.arch in LM_ARCHS:
        train_lm_online(args)
        return

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())

    api = get_model(cfg)
    rules = make_rules(cfg, mesh)
    specs = api.specs(cfg)
    params_sh = tree_shardings(specs, rules, mesh)
    opt = steps_lib.default_optimizer(cfg)

    from repro.configs.base import ShapeSuite
    shape = ShapeSuite("cli", args.seq, args.batch, "train")
    built = steps_lib.make_train_step(cfg, mesh, shape, opt)

    extra = {}
    if cfg.n_patches:
        extra["n_patches"] = cfg.n_patches
    if cfg.family == "encdec":
        extra["frames"] = (cfg.enc_seq, cfg.d_model)

    def data_at(step):
        from repro.data.tokens import _tokens_for
        it = synthetic_token_batches(args.batch, args.seq, cfg.vocab_size,
                                     seed=1234 + step, **extra)
        return {k: jnp.asarray(v) for k, v in next(it).items()}

    def make_trainer(attempt=0):
        params = materialize(specs, jax.random.key(0))
        params = jax.device_put(params, params_sh)
        # jit so every state leaf gets its own buffer (donation-safe: plain
        # jnp.zeros can alias identical constants across leaves)
        opt_state = jax.jit(opt.init)(params)
        tcfg = TrainerConfig(total_steps=args.steps,
                             ckpt_every=args.ckpt_every,
                             ckpt_dir=args.ckpt_dir,
                             fail_at_step=args.fail_at if attempt == 0 else -1,
                             metrics_path=args.metrics)

        def step_fn(params, opt_state, batch, step):
            return built.jitted(params, opt_state, batch, jnp.int32(step))

        return Trainer(tcfg, step_fn, params, opt_state, data_at)

    out = run_with_restart(make_trainer)
    obs = telemetry_from_args(args, arch=args.arch)
    finish_run(obs, f"train {args.arch}",
               {"arch": args.arch, "final_step": out["final_step"],
                "restarts": out["restarts"],
                "stragglers": out["stragglers"],
                **_loss_fields(out["metrics"])})


if __name__ == "__main__":
    main()
