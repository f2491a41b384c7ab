"""True online training over an unbounded stream — the thing RTRL buys.

BPTT must hold the whole sequence and update at its end; an RTRL learner
(`repro.core.learner`) carries an O(1)-in-T state and can hand out gradients
at ANY step.  :class:`OnlineTrainer` exercises exactly that: it consumes a
step-keyed stream `(x_t, y_t) = stream(t)`, applies an optimizer update
every `update_every` steps — mid-sequence, no sequence boundary exists —
and checkpoints the FULL learner carry (influence buffer, activity,
gradient accumulators, loss scale) plus RNG key and stream position, so a
restarted worker resumes mid-stream to bit-identical gradients
(tests/test_online.py injects a crash and proves it).

The per-update work is one jitted `lax.scan` of `learner.step` over the
k-step window followed by `learner.grads` + optimizer + `reset_grads`
(`online_update_chunk`); with `update_every=T` this reproduces the legacy
whole-sequence `*_loss_and_grads` gradients bit-for-bit — `stream_grads`
is that equivalence surface, tested for every engine x backend x
col_compact combination.

Loss convention: the learner's per-step loss is scaled by 1/t_total
(default: the update window k), so each update's summed loss is a window
mean — comparable across window sizes.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.obs import MetricPack, Telemetry
from repro.runtime.trainer import InjectedFailure

Tree = Any


def stream_grads(learner, carry: Tree, xs: jax.Array, ys: jax.Array):
    """Drive the learner over a [k]-step window and read out the gradient.

    Returns (carry, loss, grads, stats): the online code path's gradient
    computation, WITHOUT the optimizer — the equivalence surface against the
    whole-sequence wrappers (update window == T reproduces them exactly)."""
    def body(c, xy):
        c, out = learner.step(c, xy[0], xy[1])
        return c, out.stats

    carry, stats = jax.lax.scan(body, carry, (xs, ys))
    with jax.named_scope("grad_readout"):
        grads = learner.grads(carry)
    return carry, carry["loss"], grads, stats


def online_update_chunk(learner, opt, carry: Tree, opt_state: Tree,
                        xs: jax.Array, ys: jax.Array, upd: jax.Array,
                        pack: MetricPack | None = None):
    """One online update: scan the window, update params mid-stream, reset
    the accumulators (influence state carries over — the online-RTRL
    regime).  Pure; jit it once per window shape.

    With `pack` (an `repro.obs.MetricPack`) the chunk's metrics are ONE
    packed ``[F]`` float32 vector under ``metrics["packed"]`` — every
    telemetry scalar in a single device->host readback.  The pack fields
    only reduce values the chunk already computed, so the instrumented
    chunk's carry/opt_state outputs are bit-identical to pack=None
    (tests/test_obs.py pins this)."""
    carry, loss, grads, stats = stream_grads(learner, carry, xs, ys)
    with jax.named_scope("optimizer"):
        params, opt_state = opt.update(grads, opt_state,
                                       learner.params_of(carry), upd)
        carry = learner.reset_grads(carry, params)
    if pack is not None:
        packed = pack.pack({"loss": loss, "grads": grads, "stats": stats,
                            "carry": carry})
        return carry, opt_state, {"packed": packed}
    metrics = {"loss": loss}
    for k in ("alpha", "beta"):
        if k in stats:
            metrics[k] = jnp.asarray(stats[k]).mean()
    if "overflow" in stats:
        # max, not mean: any nonzero step means the window's gradients are
        # no longer exact — same semantics as the offline metrics path
        metrics["overflow"] = jnp.asarray(stats["overflow"]).max()
    return carry, opt_state, metrics


@dataclasses.dataclass
class OnlineTrainerConfig:
    total_steps: int = 170          # stream steps (not updates)
    update_every: int = 1           # optimizer update every k stream steps
    ckpt_every: int = 0             # checkpoint every N updates (0 = off)
    ckpt_dir: str = "/tmp/repro_online_ckpt"
    keep: int = 3
    log_every: int = 10             # log every N updates
    fail_at_update: int = -1        # failure injection (once)
    metrics_path: str | None = None
    seed: int = 0
    t_total: float | None = None    # per-step loss scale (None: update_every)
    straggler_factor: float = 3.0   # window counts as straggler past EMA * f


class OnlineTrainer:
    """Streaming trainer over a Learner: mid-sequence updates, O(1) memory,
    carry-inclusive checkpoints.

    stream: a step-keyed callable `t -> (x_t [B, ...], y_t [B])` so a
    restarted worker replays its exact shard (same discipline as
    `runtime.trainer.Trainer`).  Works with `run_with_restart`.

    rewire_schedule (`repro.sparsity.RewireSchedule`): prune-and-regrow
    mask evolution.  Events fire at UPDATE boundaries (right after the
    optimizer consumed and reset the gradient accumulator) via
    `learner.rewire` — the learner must be built with
    ``LearnerSpec(rewirable=True)``.  Count-preserving rewire keeps every
    carry shape static, so the jitted update chunk never recompiles; the
    mask state lives in the carry and the event counter in the checkpoint,
    so a restarted worker replays the identical mask sequence.

    guard (`repro.runtime.guard.GuardConfig`): StreamGuard fault
    resilience — fused health checks on every window, a known-good
    snapshot ring, rollback-and-replay under an escalating degradation
    policy.  fault_plan (`guard.FaultPlan`): deterministic fault
    injection for tests/CI.  shardings: optional leaf-complete tree of
    target shardings over `_ckpt_tree()` for elastic re-mesh resume.

    frozen: the weights of blocks that run forward only (a learner whose
    cell has them, `repro.cells.mamba2`); they ride in the carry, and the
    trainer owns them from here on.  The unguarded chunk donates the carry
    and the optimizer state, so the influence state is updated in place
    and never held twice; the trainer copies any carry leaf that is one of
    the caller's params or masks first, so those stay the caller's.
    Under an enabled tracer the chunk's stage map is noted as
    `"online_chunk"` after its first dispatch (`Tracer.note_program`)."""

    PROGRAM = "online_chunk"

    def __init__(self, cfg: OnlineTrainerConfig, learner, opt, params: Tree,
                 masks: Tree | None, stream: Callable[[int], tuple],
                 rewire_schedule=None, guard=None, fault_plan=None,
                 shardings: Tree | None = None, telemetry=None,
                 frozen: Tree | None = None):
        self.cfg = cfg
        self.learner = learner
        self.opt = opt
        # telemetry (repro.obs.Telemetry) is never None past this line: the
        # null form keeps a live registry (every report sources from it)
        # but writes no files; the in-jit MetricPack compiles into the
        # chunk only when exporters are on, so the default path stays the
        # uninstrumented chunk
        self.obs = telemetry if telemetry is not None else Telemetry.null()
        self._pack = MetricPack.default() if self.obs.active else None
        self._last_packed: dict | None = None
        self._fault_plan = fault_plan
        if fault_plan is not None:
            stream = fault_plan.wrap_stream(stream)
        self.stream = stream
        self.shardings = shardings      # leaf-complete over _ckpt_tree()
        x0, y0 = stream(0)
        tt = cfg.t_total if cfg.t_total is not None else float(cfg.update_every)
        extra = {} if frozen is None else {"frozen": frozen}
        carry = learner.init(params, masks,
                             (jnp.asarray(x0), jnp.asarray(y0)),
                             t_total=tt, **extra)
        self.carry = _owned(carry, (params, masks))
        self._noted = False
        self.opt_state = jax.jit(opt.init)(params)
        if rewire_schedule is not None:
            # fail at construction, not at the first event hours into a run
            if "rw" not in self.carry:
                raise ValueError(
                    "rewire_schedule requires a rewirable learner — "
                    "construct it with LearnerSpec(rewirable=True)")
            if not (isinstance(self.opt_state, dict)
                    and "mask" in self.opt_state):
                # a closure-masked (or unmasked) optimizer would keep stale
                # moments alive at pruned positions and pin grown weights
                # at 0
                raise ValueError(
                    "rewire_schedule requires a masked_dynamic optimizer "
                    "(the mask must live in the optimizer state so rewire "
                    "events can swap it) — see "
                    "repro.optim.optimizers.masked_dynamic")
        self.step = 0                     # stream position
        self.update = 0                   # optimizer updates applied
        self.key = jax.random.key(cfg.seed)
        self.rewire_schedule = rewire_schedule
        self.rewire_events = 0            # events fired (checkpointed)
        self._rewire_base = jax.random.key(cfg.seed)
        write_fault = (fault_plan.ckpt_write_fault
                       if fault_plan is not None
                       and fault_plan.fail_ckpt_writes > 0 else None)
        self.ckpt = (CheckpointManager(
            cfg.ckpt_dir, keep=cfg.keep,
            retries=(guard.ckpt_retries if guard is not None else 0),
            write_fault=write_fault)
            if cfg.ckpt_every > 0 else None)
        self.metrics: list[dict] = []
        self._failed_once = False
        self._dt_ema: float | None = None
        pack = self._pack
        self._chunk = jax.jit(
            lambda carry, opt_state, xs, ys, upd: online_update_chunk(
                learner, opt, carry, opt_state, xs, ys, upd, pack=pack),
            donate_argnums=(0, 1))
        self.guard = None
        if guard is not None:
            # lazy import: guard.py imports this module at its top level
            from repro.runtime.guard import (StreamGuard, advance_chunk,
                                             guarded_update_chunk)
            self.guard = StreamGuard(guard, telemetry=self.obs)
            self._gchunk = jax.jit(
                lambda carry, opt_state, xs, ys, upd, clip:
                guarded_update_chunk(learner, opt, carry, opt_state,
                                     xs, ys, upd, clip, pack=pack))
            self._advance = jax.jit(
                lambda carry, xs, ys: advance_chunk(learner, carry, xs, ys))

    # -- checkpoint/restore: carry + opt + RNG + stream position ------------

    def _ckpt_tree(self) -> Tree:
        return {"carry": self.carry, "opt": self.opt_state,
                "pos": jnp.int32(self.step),
                "rewire_events": jnp.int32(self.rewire_events),
                "key": jax.random.key_data(self.key)}

    @property
    def stragglers(self) -> int:
        """Straggler windows so far (registry-backed; kept as an attribute
        for the result dict and external watchdogs)."""
        return int(self.obs.registry.counter("stragglers_total").value)

    def save(self):
        if self.ckpt is not None:
            with self.obs.span("ckpt_write", step=self.step):
                self.ckpt.save(self.update, self._ckpt_tree(),
                               extra={"step": self.step})
            self.obs.registry.counter("ckpt_writes_total").inc()
            self.obs.emit("ckpt_write", step=self.step, update=self.update)

    def try_resume(self) -> bool:
        if self.ckpt is None or self.ckpt.latest_step() < 0:
            return False
        # elastic re-mesh: target shardings (possibly for a different mesh
        # than the checkpoint's writer ran on) are recomputed here, never
        # read from disk — same contract as Trainer.try_resume.  The
        # shardings tree must be leaf-complete over _ckpt_tree() (None
        # entries would be dropped by tree flattening and misalign leaves).
        tree, upd = self.ckpt.restore(self._ckpt_tree(), self.shardings)
        if tree is None:
            return False
        self.carry, self.opt_state = _owned((tree["carry"], tree["opt"]))
        self.step = int(tree["pos"])
        self.update = upd
        self.rewire_events = int(tree["rewire_events"])
        self.key = jax.random.wrap_key_data(
            jnp.asarray(jax.device_get(tree["key"])))
        return True

    def _restore_snapshot(self, snap):
        """Roll back to a StreamGuard ring snapshot (host or device tree)."""
        tree = jax.tree.map(jnp.asarray, snap.tree)
        self.carry, self.opt_state = tree["carry"], tree["opt"]
        self.step = snap.step
        self.update = snap.update
        self.rewire_events = snap.rewire_events
        self.key = jax.random.wrap_key_data(tree["key"])

    # -- dynamic sparsity ---------------------------------------------------

    def _maybe_rewire(self) -> dict:
        """Fire a prune-and-regrow event if the schedule says so.  Returns
        metric entries for the log (empty when no event fired)."""
        sch = self.rewire_schedule
        if sch is None or not sch.fires(self.update):
            return {}
        from repro.optim.optimizers import set_opt_mask
        t0 = time.perf_counter()
        ev = self.rewire_events
        with self.obs.span("rewire", event=ev):
            self.carry = self.learner.rewire(
                self.carry, sch.event_key(self._rewire_base, ev),
                frac=sch.fraction(ev), method=sch.method, block=sch.block)
            if isinstance(self.opt_state, dict) and "mask" in self.opt_state:
                self.opt_state = set_opt_mask(
                    self.opt_state, self.learner.opt_mask_of(self.carry))
            # the optimizer's mask may be the carry's own arrays
            self.carry, self.opt_state = _owned((self.carry, self.opt_state))
        self.rewire_events = ev + 1
        fp = self.carry_nbytes()
        ms = round((time.perf_counter() - t0) * 1e3, 2)
        reg = self.obs.registry
        reg.gauge("rewire_events").set(self.rewire_events)
        reg.gauge("carry_live_bytes").set(fp["live"])
        reg.gauge("carry_col_density").set(fp["col_density"])
        self.obs.emit("rewire", event=ev, frac=sch.fraction(ev), ms=ms,
                      carry_live_bytes=fp["live"],
                      col_density=fp["col_density"])
        return {"rewire_event": ev, "rewire_frac": round(sch.fraction(ev), 5),
                "rewire_ms": ms, "carry_live_bytes": fp["live"]}

    def carry_nbytes(self) -> dict:
        """{'alloc', 'live', 'col_density'}: the carry's allocated bytes vs
        its LIVE footprint, pricing each influence buffer at its live column
        count (`costs.carry_footprint` — the O(w~ beta~ n p) claim), so
        rewire events report the true footprint rather than the init-time
        allocation width.  Stacked buffers are priced per layer: layer l's
        buffer structurally zeroes the columns of layers j > l, so its live
        width is the <= l share of the shared compact axis."""
        from repro.core.costs import carry_footprint
        c = self.carry
        total = carry_nbytes(c)
        out = {"alloc": total, "live": total, "col_density": 1.0}
        rw = c.get("rw") if isinstance(c, dict) else None
        if rw is None:
            return out
        if "cl" in rw:
            live_v = np.asarray(rw["cl"]["live"])
            layer_v = np.asarray(rw["cl"]["layer"])
            n_cols = live_v.shape[-1]
            n_live = int(live_v.sum())
            layer_live = lambda l: int((live_v * (layer_v <= l)).sum())
        elif "colm" in rw:
            colm = np.asarray(rw["colm"])
            n_cols, n_live = colm.shape[-1], int(colm.sum())
            layer_live = lambda l: n_live
        elif "colms" in rw:
            colms = [np.asarray(cm) for cm in rw["colms"]]
            n_cols, n_live = colms[-1].shape[-1], int(colms[-1].sum())
            layer_live = lambda l: int(colms[l].sum())
        else:
            return out
        bufs = []                                    # (buffer, layer-or-None)
        for holder in (c, c.get("state") or {}):
            for k in ("vals", "M"):
                src = holder.get(k)
                if src is None:
                    continue
                bufs += ([(b, l) for l, b in enumerate(src)]
                         if isinstance(src, tuple) else [(src, None)])
        live_total = total
        for b, l in bufs:
            if hasattr(b, "shape") and b.shape[-1] == n_cols:
                rows = b.size // n_cols
                nl = n_live if l is None else layer_live(l)
                fp = carry_footprint(1, rows, n_cols, nl)
                live_total += fp["live_bytes"] - fp["alloc_bytes"]
        out["live"] = live_total
        out["col_density"] = n_live / n_cols
        return out

    def row_stats(self) -> dict | None:
        """Per-example active-row stats of a compact influence carry, or
        None off the compact backends.  K_b = live rows of example b's
        influence; 'ragged_utilization' = Sigma_b K_b / (B * K_max) — the
        fraction of the batch-wide capacity rectangle that is actually
        live.  The gap to 1.0 is the batch tax the fused ragged kernel
        skips (it executes Sigma_b K_b K'_b Pc, not B K_max^2 Pc).  Also
        reports the carry dtype (the opt-in bf16 carry halves bytes)."""
        c = self.carry
        bufs = []                               # (idx [B, K], vals dtype)
        for holder in (c, c.get("state") or {}):
            idx, vals = holder.get("idx"), holder.get("vals")
            if idx is None:
                continue
            bufs += (list(zip(idx, vals)) if isinstance(idx, tuple)
                     else [(idx, vals)])
        if not bufs:
            return None
        kbs, cap = [], 0
        for idx, _ in bufs:
            a = np.asarray(jax.device_get(idx))
            kbs.append((a >= 0).sum(axis=1))
            cap += a.size                       # B * K of this buffer
        kb = np.concatenate(kbs)
        return {"k_min": int(kb.min()), "k_mean": round(float(kb.mean()), 2),
                "k_max": int(kb.max()),
                "ragged_utilization": round(float(kb.sum()) / cap, 4),
                "influence_dtype": str(np.asarray(
                    jax.device_get(bufs[0][1])).dtype)}

    # -- loop ---------------------------------------------------------------

    def _gather(self, start: int, k: int):
        xs, ys = zip(*(self.stream(start + i) for i in range(k)))
        return (jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys)))

    def _watch_straggler(self, dt: float):
        """EMA watchdog over window wall time (same scheme as Trainer): a
        window slower than straggler_factor x the EMA counts as a straggler."""
        if self._dt_ema is None:
            self._dt_ema = dt
            return
        if dt > self.cfg.straggler_factor * self._dt_ema:
            self.obs.registry.counter("stragglers_total").inc()
        self._dt_ema = 0.9 * self._dt_ema + 0.1 * dt

    def _execute_window(self, start: int, k: int):
        """Execute one update window under the guard's pending degradation
        (if any).  Returns (ok, metrics, guard_rec); ok=False means the
        window faulted and the trainer was rolled back — re-enter the loop
        and this window re-executes (deterministic replay) one rung up the
        escalation ladder."""
        g = self.guard
        self._last_packed = None
        action = None if g is None else g.pending_action(start)
        if action == "quarantine":
            # persistent data fault: drop the window's inputs entirely;
            # carry/params/opt are untouched, the stream skips past it
            g.note_quarantine(start, k, self.update)
            return True, {}, {"guard_action": action}
        xs, ys = self._gather(start, k)
        if g is None:
            args = (self.carry, self.opt_state, xs, ys,
                    jnp.int32(self.update))
            shapes = None
            if not self._noted and self.obs.tracer.enabled:
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    args)
            self.carry, self.opt_state, m = self._chunk(*args)
            if shapes is not None:
                # after the first dispatch: the compile is served from the
                # caches
                self.obs.tracer.note_program(self.PROGRAM, self._chunk,
                                             *shapes)
                self._noted = True
            if self._pack is not None:
                # THE window readback: one packed vector, blocks like the
                # loss fetch it replaces
                pk = self._pack.unpack(m["packed"])
                self._last_packed = pk
                return True, _legacy_metrics(pk), {}
            jax.block_until_ready(m["loss"])
            return True, m, {}
        if action == "skip_update":
            carry, m = self._advance(self.carry, xs, ys)
            fault = g.check(m, self.update)
            if fault is not None:
                g.on_fault(self, fault)
                return False, None, None
            self.carry = carry
        else:
            # 'clip' degrades; clip=+inf is EXACTLY factor 1.0, so the
            # healthy path stays bit-identical to the unguarded chunk
            clip = jnp.float32(g.cfg.clip_norm if action == "clip"
                               else np.inf)
            carry, opt_state, m = self._gchunk(
                self.carry, self.opt_state, xs, ys,
                jnp.int32(self.update), clip)
            if self._pack is not None:
                # one readback serves guard AND telemetry: unpack the vec,
                # hand the guard plain floats (its dict branch passes them
                # through)
                pk = self._pack.unpack(m["packed"])
                fault = g.check({"health": pk["health"], "loss": pk["loss"],
                                 "overflow": pk["overflow"]}, self.update)
                if fault is not None:
                    g.on_fault(self, fault)
                    return False, None, None
                self.carry, self.opt_state = carry, opt_state
                self._last_packed = pk
                return True, _legacy_metrics(pk), (
                    {"guard_action": action} if action else {})
            fault = g.check(m, self.update)
            if fault is not None:
                g.on_fault(self, fault)
                return False, None, None
            self.carry, self.opt_state = carry, opt_state
        m = dict(m)
        m.pop("health", None)
        m.pop("verdict", None)
        return True, m, ({"guard_action": action} if action else {})

    def run(self) -> dict:
        cfg = self.cfg
        if self.guard is not None and not self.guard.ring:
            self.guard.push(self)         # initial known-good restore point
        while self.step < cfg.total_steps:
            if self.update == cfg.fail_at_update and not self._failed_once:
                self._failed_once = True
                raise InjectedFailure(
                    f"injected failure at update {self.update} "
                    f"(stream step {self.step})")
            if self._fault_plan is not None:
                self._fault_plan.maybe_crash(self.update)
            k = min(cfg.update_every, cfg.total_steps - self.step)
            start = self.step
            t0 = time.perf_counter()
            with self.obs.span("window", update=self.update, step=start):
                ok, m, guard_rec = self._execute_window(start, k)
            if not ok:
                continue                  # rolled back; window re-executes
            dt = time.perf_counter() - t0
            self._watch_straggler(dt)
            self.step = start + k
            self.update += 1
            self.key = jax.random.fold_in(self.key, self.update)
            self.obs.record_window(self.update, self.step, dt * 1e3,
                                   packed=self._last_packed, **guard_rec)
            rewire_rec = self._maybe_rewire()
            if self.guard is not None:
                # commit AFTER rewire so snapshots carry post-event masks
                # and the matching event counter
                self.guard.commit(self, start)
            if self._fault_plan is not None:
                self._fault_plan.maybe_corrupt(self)
            if self.ckpt is not None and self.update % cfg.ckpt_every == 0:
                self.save()
            if (rewire_rec or guard_rec or self.update % cfg.log_every == 0
                    or self.step >= cfg.total_steps):
                rec = {"update": self.update, "step": self.step,
                       "dt_s": round(dt, 4), **rewire_rec, **guard_rec,
                       **{k_: float(np.asarray(v)) for k_, v in m.items()}}
                self.metrics.append(rec)
                if cfg.metrics_path:
                    with open(cfg.metrics_path, "a") as f:
                        f.write(json.dumps(rec) + "\n")
        self.save()
        if self.ckpt is not None:
            self.ckpt.wait()
        # land the run-level numbers on the registry, then source the
        # result dict FROM it — keys stay what they always were, but the
        # registry / Prometheus exposition / manifest can never disagree
        # with the return value
        fp = self.carry_nbytes()
        reg = self.obs.registry
        reg.gauge("final_step").set(self.step)
        reg.gauge("updates").set(self.update)
        reg.gauge("rewire_events").set(self.rewire_events)
        reg.gauge("carry_alloc_bytes").set(fp["alloc"])
        reg.gauge("carry_live_bytes").set(fp["live"])
        reg.gauge("carry_col_density").set(fp["col_density"])
        out = {"final_step": int(reg.gauge("final_step").value),
               "updates": int(reg.gauge("updates").value),
               "metrics": self.metrics,
               "rewire_events": int(reg.gauge("rewire_events").value),
               "carry_bytes": int(reg.gauge("carry_alloc_bytes").value),
               "carry_live_bytes": int(reg.gauge("carry_live_bytes").value),
               "stragglers": self.stragglers}
        rs = self.row_stats()
        if rs is not None:
            out["row_stats"] = rs
        if self.guard is not None:
            out["guard"] = self.guard.report()
        return out


def _owned(tree: Tree, theirs: Tree = ()) -> Tree:
    """`tree` with a copy of every leaf that is one of `theirs`' leaves or
    repeats an earlier leaf: a donated tree may hold each buffer once, and
    none that its caller still holds."""
    seen = {id(x) for x in jax.tree.leaves(theirs)}

    def own(x):
        if id(x) in seen:
            return jnp.copy(x)
        seen.add(id(x))
        return x
    return jax.tree.map(own, tree)


def _legacy_metrics(pk: dict) -> dict:
    """Unpacked MetricPack dict -> the chunk-metrics keys the log records
    always carried (loss / alpha / beta / overflow).  NaN fields are the
    pack's 'not applicable to this engine' marker — dropped, matching the
    uninstrumented chunk's key-presence behavior."""
    m = {"loss": pk["loss"]}
    for src, dst in (("act_sparsity", "alpha"), ("bwd_sparsity", "beta"),
                     ("overflow", "overflow")):
        v = pk.get(src)
        if v is not None and not np.isnan(v):
            m[dst] = v
    return m


def carry_nbytes(carry: Tree) -> int:
    """Total bytes held by the learner carry — the O(1)-in-stream-length
    memory claim, as a number callers can assert on and logs can report.
    From the leaves' shapes and dtypes: nothing is read back."""
    return int(sum(x.nbytes if hasattr(x, "nbytes") else np.asarray(x).nbytes
                   for x in jax.tree.leaves(carry)))
