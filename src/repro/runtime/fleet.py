"""Multi-tenant stream fleet: vmapped carry batching for concurrent
online-RTRL sessions.

The O(1)-in-T influence carry makes a *personally adapting* RNN per user
affordable — but `OnlineTrainer` drives exactly one stream, so serving S
users costs S dispatches of a small jitted chunk whose wall clock is
dominated by per-op overhead, not FLOPs.  :class:`StreamFleet` stacks S
independent sessions — (params, opt state, learner carry, stream position)
each — along a leading *slot* axis and drives them all through ONE shared
jitted update chunk: `jax.vmap` of `online_update_chunk` over the slot
axis.  Per-session cost then approaches the marginal cost of one more
batch row instead of one more dispatch (`benchmarks/fleet_bench.py`
measures the sessions/sec scaling and asserts the fleet-64 >= 8x bar).

Slot-based continuous batching, same discipline as `runtime/serving.py`:

- the fleet shape (S, window k, per-session batch B) is STATIC — sessions
  join and leave mid-flight at different stream positions with zero
  recompilation;
- dead slots are DON'T-CARE lanes: vmapped per-slot computation is
  lane-independent (elementwise ops and per-lane reductions round
  identically whatever the other lanes hold), so a dead lane grinding on
  throwaway state cannot perturb a live lane's bits.  The `live` mask
  gates stats and host bookkeeping only; a join overwrites the slot's
  buffers wholesale and a leave resets them to the template, so dead-lane
  contents are never observed and never drift unboundedly.  (The obvious
  alternative — a `jnp.where` live-select restoring dead slots' pre-window
  state — is NOT used: any large-tensor consumer added after the vmapped
  chunk changes how XLA:CPU compiles the chunk's own reductions, ulp-
  shifting e.g. the adamw bias updates even behind an
  `optimization_barrier`, which would break fleet-of-1 bit-identity with
  the solo trainer.  A mask-only consumer of the scalar metrics is
  measured clean; tests/test_fleet.py pins this.);
- joins from the template and leaves touch no device buffer: they mark
  their slot pending on the host, and before the next window ONE donated
  program (`jit_slot_reset`) writes the template into every pending slot
  of both stacks at once.  Every read of the stacks (`carry`,
  `opt_state`, `slot_state`, `evict`) applies pending resets first, and an
  explicit install (`add_session(params=)`, `resume`) cancels its slot's,
  so what any read sees is what a per-session write would have left;
- idle sessions EVICT their full {carry, opt state, stream position,
  update count} to the session-keyed checkpoint store
  (`repro.checkpoint.save_session`) and later resume bit-for-bit — the
  same carry-inclusive restart contract `OnlineTrainer` checkpoints prove
  per-stream, namespaced per session id.

Memory and sync posture: the stacked buffers are DONATED through the
chunk (fleet memory stays 1x, not 2x), and the steady-state loop performs
a single packed [S, 3] readback per window — live flag, window loss,
compact-capacity overflow — the same fused-verdict trick as `guard.py`.

Every session shares one learner (one engine, one set of parameter-
sparsity masks: the compact column layout is compiled into the chunk) and
one optimizer; sessions differ in parameter VALUES, carry, optimizer
moments and stream position.  A fleet of 1 is bit-identical to the solo
`OnlineTrainer` (tests/test_fleet.py).
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_session, save_session
from repro.obs import MetricPack, Telemetry
from repro.runtime.online import carry_nbytes, online_update_chunk

Tree = Any


def fleet_update_chunk(learner, opt, carry: Tree, opt_state: Tree,
                       xs: jax.Array, ys: jax.Array, upd: jax.Array,
                       live: jax.Array, pack=None):
    """One update window for every slot at once.

    carry/opt_state: slot-stacked trees (leading axis S).  xs [S, k, B, ...],
    ys [S, k, B], upd [S] int32 (per-slot optimizer update counts — slots
    joined at different times), live [S] bool.

    vmaps `online_update_chunk` over the slot axis.  Every lane — live or
    dead — runs the chunk; dead lanes grind on don't-care state (the host
    feeds them zero inputs) whose outputs are simply never observed.  The
    `live` mask only gates the metrics: the packed [S, 3] float32 rows are
    [live, loss * live, overflow * live] — the single per-window readback.
    With `pack` (a `repro.obs.MetricPack`) each row grows to [S, 3 + F]:
    the same three columns followed by the slot's full telemetry vector —
    still ONE readback, now carrying every per-session metric.

    No per-leaf live-select restores dead slots' pre-window state on
    purpose: consuming the chunk's large output tensors with ANY extra op
    (a `jnp.where` select, even behind `jax.lax.optimization_barrier`)
    changes how XLA:CPU blocks the chunk's internal reductions and ulp-
    shifts its results, breaking the fleet's bit-identity with the solo
    trainer.  Scalar-metrics consumers are measured clean — the MetricPack
    fields are per-lane scalar reductions inside the vmapped chunk, pinned
    bit-identical by tests/test_obs.py.  Pure; jit with
    donate_argnums=(0, 1) so fleet memory stays 1x.
    """
    carry, opt_state, m = jax.vmap(
        lambda c, o, x, y, u: online_update_chunk(learner, opt, c, o, x, y, u,
                                                  pack=pack)
    )(carry, opt_state, xs, ys, upd)
    with jax.named_scope("telemetry"):
        lf = live.astype(jnp.float32)
        if pack is not None:
            vec = m["packed"]                           # [S, F]
            loss = vec[:, pack.names.index("loss")] * lf
            ov_col = vec[:, pack.names.index("overflow")]
            ov = jnp.where(jnp.isnan(ov_col), 0.0, ov_col) * lf
            packed = jnp.concatenate(
                [jnp.stack([lf, loss, ov], axis=-1), vec], axis=-1)
            return carry, opt_state, packed
        loss = jnp.asarray(m["loss"], jnp.float32) * lf
        ov = (jnp.asarray(m["overflow"], jnp.float32) * lf
              if "overflow" in m else jnp.zeros_like(lf))
        packed = jnp.stack([lf, loss, ov], axis=-1)
    return carry, opt_state, packed


@dataclasses.dataclass
class FleetConfig:
    slots: int = 8                  # S: static fleet width
    update_every: int = 8           # k: stream steps per window/update
    store_dir: str | None = None    # session eviction store (None: no evict)
    t_total: float | None = None    # per-step loss scale (None: update_every)
    seed: int = 0


@dataclasses.dataclass
class _Session:
    sid: str
    stream: Callable[[int], tuple]
    slot: int
    pos: int = 0                    # stream position
    upd: int = 0                    # optimizer updates applied
    loss: float = float("nan")      # last window loss (from the packed row)
    overflow: float = 0.0           # last window compact-capacity overflow


class StreamFleet:
    """S concurrent online-RTRL sessions behind one compiled update chunk.

    learner/opt/masks are shared by every session (the masks' compact
    column layout is baked into the compiled chunk — `_freeze_static`
    requires one masks object identity); `params` seeds the slot template
    and is the default init for joining sessions.  `example` is one
    (x_0, y_0) batch fixing the per-session stream shapes.

    API: `add_session(sid, stream, params=)` claims a free slot (traced
    slot index — no recompile), `evict(sid)` writes the session's full
    state to the store and frees its slot, `resume(sid, stream)` loads it
    back bit-for-bit into any free slot, `step_window()` advances every
    live session by one k-step window.  A join from the template and a
    leave only mark their slot pending; `step_window` resets all pending
    slots with one `jit_slot_reset` dispatch before its chunk, and any
    read of `carry`, `opt_state` or `slot_state` applies them first.
    """

    def __init__(self, cfg: FleetConfig, learner, opt, params: Tree,
                 masks: Tree | None, example: tuple, telemetry=None):
        self.cfg = cfg
        self.learner = learner
        self.opt = opt
        self.masks = masks
        self.obs = telemetry if telemetry is not None else Telemetry.null()
        # per-session telemetry columns only when exporters are on: the
        # bench path keeps the lean [S, 3] readback
        self._pack = MetricPack.default() if self.obs.active else None
        S = cfg.slots
        x0, y0 = example
        tt = (cfg.t_total if cfg.t_total is not None
              else float(cfg.update_every))
        self._t_total = tt
        self._x0 = jnp.asarray(x0)
        self._y0 = jnp.asarray(y0)
        carry0 = learner.init(params, masks, (self._x0, self._y0), t_total=tt)
        opt0 = jax.jit(opt.init)(params)
        self._template = (carry0, opt0)
        self.session_carry_bytes = carry_nbytes(carry0)

        # slot-stacked state.  Stack under jit, then de-alias: XLA may give
        # identical constants (two all-zero leaves) one buffer, which would
        # break donation (same buffer donated twice) — .copy() forces each
        # leaf to own its storage (same trick as runtime/serving.py).
        stack = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.repeat(x[None], S, 0), t))((carry0, opt0))
        self._carry, self._opt_state = jax.tree.map(lambda x: x.copy(),
                                                    stack)

        self.sessions: dict[str, _Session] = {}
        self._free = list(range(S))     # heap of free slots
        self._pending: set[int] = set() # slots owed a template reset
        self.windows = 0

        pack = self._pack

        # named programs: the device trace and JAX's dispatch annotations
        # say jit_fleet_chunk, jit_slot_write, jit_slot_read, jit_slot_reset
        def fleet_chunk(carry, opt_state, xs, ys, upd, live):
            return fleet_update_chunk(learner, opt, carry, opt_state, xs, ys,
                                      upd, live, pack=pack)

        # traced slot index: one compile serves every slot
        def slot_write(stacked, tree, i):
            return jax.tree.map(
                lambda b, v: jax.lax.dynamic_update_index_in_dim(
                    b, v.astype(b.dtype), i, 0), stacked, tree)

        def slot_read(stacked, i):
            return jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(b, i, 0,
                                                       keepdims=False),
                stacked)

        # a fixed-shape [S] mask: one compile serves any set of slots; the
        # select runs in place on the donated stacks
        def slot_reset(carry, opt_state, mask, template):
            def put(b, t):
                m = mask.reshape((-1,) + (1,) * t.ndim)
                return jnp.where(m, t[None], b)
            return (jax.tree.map(put, carry, template[0]),
                    jax.tree.map(put, opt_state, template[1]))

        self._chunk = jax.jit(fleet_chunk, donate_argnums=(0, 1))
        self._write = jax.jit(slot_write, donate_argnums=(0,))
        self._read = jax.jit(slot_read)
        self._reset = jax.jit(slot_reset, donate_argnums=(0, 1))

    # -- the slot-stacked state ---------------------------------------------

    @property
    def carry(self) -> Tree:
        """The slot-stacked learner carry, pending resets applied."""
        self._apply_resets()
        return self._carry

    @property
    def opt_state(self) -> Tree:
        """The slot-stacked optimizer state, pending resets applied."""
        self._apply_resets()
        return self._opt_state

    def _apply_resets(self):
        """Write the template into every pending slot: one dispatch, not
        waited on."""
        if not self._pending:
            return
        n = len(self._pending)
        with self.obs.span("fleet.slot_reset", slots=n):
            mask = np.zeros((self.cfg.slots,), bool)
            mask[list(self._pending)] = True
            self._carry, self._opt_state = self._reset(
                self._carry, self._opt_state, mask, self._template)
            self._pending.clear()
            self.obs.registry.counter("fleet_slot_resets_total").inc(n)

    # -- slot management ----------------------------------------------------

    @property
    def n_live(self) -> int:
        return len(self.sessions)

    def free_slots(self) -> list[int]:
        return sorted(self._free)

    def _claim(self, sid: str) -> int:
        """The slot a joining session will take: the least free one."""
        if sid in self.sessions:
            raise ValueError(f"session {sid!r} already in the fleet")
        if not self._free:
            raise ValueError(f"fleet is full ({self.cfg.slots} slots); "
                             "evict a session first")
        return self._free[0]

    def _install(self, sess: _Session, carry: Tree | None = None,
                 opt_state: Tree | None = None):
        """Give `sess` the slot `_claim` gave out.  With no state, the slot
        is marked for the next template reset (no device work); with a
        state, its pending reset is cancelled and the state written into
        the slot directly (it differs per session)."""
        if carry is None:
            self._pending.add(sess.slot)
        else:
            self._pending.discard(sess.slot)    # the write covers the slot
            i = jnp.int32(sess.slot)
            self._carry = self._write(self._carry, carry, i)
            self._opt_state = self._write(self._opt_state, opt_state, i)
        heapq.heappop(self._free)       # the slot _claim gave out
        self.sessions[sess.sid] = sess

    def add_session(self, sid: str, stream: Callable[[int], tuple],
                    params: Tree | None = None) -> int:
        """Join a fresh session mid-flight: new carry + opt state from
        `params`, written into the slot now, or by default the fleet's
        template, written with every other pending slot by the next
        window's one reset.  Returns the claimed slot.  No recompilation —
        the slot index is traced and the fleet shape is static."""
        slot = self._claim(sid)
        with self.obs.span("fleet.admit", sid=sid, slot=slot):
            sess = _Session(sid, stream, slot)
            if params is None:
                self._install(sess)
            else:
                carry = self.learner.init(params, self.masks,
                                          (self._x0, self._y0),
                                          t_total=self._t_total)
                opt_state = jax.jit(self.opt.init)(params)
                self._install(sess, carry, opt_state)
            self.obs.registry.counter("sessions_joined_total").inc()
            self.obs.registry.gauge("sessions_live").set(self.n_live)
            self.obs.emit("session_join", sid=sid, slot=slot)
        return slot

    def remove(self, sid: str):
        """Leave without persisting (abandoned session).  The freed slot is
        marked for the next window's template reset, so the now-dead lane
        keeps grinding on bounded values (its results are don't-care, but
        NaN/Inf drift on abandoned garbage is not worth carrying); no
        device work here."""
        sess = self.sessions.pop(sid)
        with self.obs.span("fleet.retire", sid=sid, slot=sess.slot):
            heapq.heappush(self._free, sess.slot)
            self._pending.add(sess.slot)
            self.obs.registry.counter("sessions_left_total").inc()
            self.obs.registry.gauge("sessions_live").set(self.n_live)
            self.obs.emit("session_leave", sid=sid, slot=sess.slot)

    def slot_state(self, sid: str) -> tuple[Tree, Tree]:
        """(carry, opt_state) of one session, read out of the stack."""
        sess = self.sessions[sid]
        return (self._read(self.carry, jnp.int32(sess.slot)),
                self._read(self.opt_state, jnp.int32(sess.slot)))

    # -- evict / resume: the session-keyed checkpoint store -----------------

    def _store(self) -> str:
        if self.cfg.store_dir is None:
            raise ValueError("FleetConfig.store_dir is unset — evict/resume "
                             "needs a session store")
        return self.cfg.store_dir

    def evict(self, sid: str) -> int:
        """Persist the session's FULL state — carry (params + influence +
        accumulators), optimizer moments, stream position, update count —
        under `store_dir/session/<sid>/` and free its slot.  Returns the
        stream position it will resume from."""
        store = self._store()
        sess = self.sessions[sid]
        carry, opt_state = self.slot_state(sid)
        tree = {"carry": carry, "opt": opt_state,
                "pos": jnp.int32(sess.pos), "upd": jnp.int32(sess.upd)}
        save_session(store, sid, tree, step=sess.upd,
                     extra={"pos": sess.pos})
        self.remove(sid)
        self.obs.registry.counter("sessions_evicted_total").inc()
        self.obs.emit("session_evict", sid=sid, pos=sess.pos)
        return sess.pos

    def resume(self, sid: str, stream: Callable[[int], tuple]) -> int:
        """Load an evicted session back into any free slot, bit-for-bit:
        same carry, same moments, same stream position.  Returns the slot."""
        store = self._store()
        slot = self._claim(sid)
        with self.obs.span("fleet.admit", sid=sid, slot=slot):
            like = {"carry": self._template[0], "opt": self._template[1],
                    "pos": jnp.int32(0), "upd": jnp.int32(0)}
            tree, _ = load_session(store, sid, like)
            sess = _Session(sid, stream, slot,
                            pos=int(tree["pos"]), upd=int(tree["upd"]))
            self._install(sess, tree["carry"], tree["opt"])
            self.obs.registry.counter("sessions_resumed_total").inc()
            self.obs.registry.gauge("sessions_live").set(self.n_live)
            self.obs.emit("session_resume", sid=sid, slot=slot, pos=sess.pos)
        return slot

    # -- the steady-state loop ----------------------------------------------

    def _gather(self, k: int):
        """Host-side input assembly: every live session contributes its own
        next k stream steps AT ITS OWN POSITION; dead slots get zeros
        (their lanes' outputs are don't-care and never read)."""
        S = self.cfg.slots
        xs = np.zeros((S, k) + tuple(self._x0.shape), self._x0.dtype)
        ys = np.zeros((S, k) + tuple(self._y0.shape), self._y0.dtype)
        upd = np.zeros((S,), np.int32)
        live = np.zeros((S,), bool)
        for sess in self.sessions.values():
            for i in range(k):
                x, y = sess.stream(sess.pos + i)
                xs[sess.slot, i] = x
                ys[sess.slot, i] = y
            upd[sess.slot] = sess.upd
            live[sess.slot] = True
        return xs, ys, upd, live

    def step_window(self) -> dict[str, dict]:
        """Advance every live session by one k-step window + one optimizer
        update.  ONE dispatch, ONE packed [S, 3] readback — the loop stays
        free of per-session host syncs.  Returns {sid: {loss, overflow,
        pos, upd[, telemetry]}} for the window."""
        k = self.cfg.update_every
        span = self.obs.span
        self._apply_resets()
        with span("fleet.gather", live=len(self.sessions)):
            xs, ys, upd, live = self._gather(k)
        t0 = time.perf_counter()
        with span("window", window=self.windows, live=int(live.sum())):
            args = (jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(upd),
                    jnp.asarray(live))
            self._carry, self._opt_state, packed = self._chunk(
                self._carry, self._opt_state, *args)
            pk = np.asarray(jax.device_get(packed))     # the single readback
        dt_ms = (time.perf_counter() - t0) * 1e3
        tracer = self.obs.tracer
        if tracer.enabled and "fleet_chunk" not in tracer.programs:
            # once, after the first dispatch: the compile is cached
            tracer.note_program("fleet_chunk", self._chunk, self._carry,
                                self._opt_state, *args)
        self.windows += 1
        reg = self.obs.registry
        reg.counter("fleet_windows_total").inc()
        reg.histogram("fleet_window_ms").observe(dt_ms)
        out = {}
        with span("fleet.bookkeep", live=len(self.sessions)):
            for sess in self.sessions.values():
                sess.pos += k
                sess.upd += 1
                sess.loss = float(pk[sess.slot, 1])
                sess.overflow = float(pk[sess.slot, 2])
                out[sess.sid] = {"loss": sess.loss,
                                 "overflow": sess.overflow,
                                 "pos": sess.pos, "upd": sess.upd}
                if self._pack is not None:
                    # the [3:] tail is the slot's full MetricPack vector,
                    # no extra readback
                    out[sess.sid]["telemetry"] = self._pack.unpack(
                        pk[sess.slot, 3:])
            self.obs.emit("fleet_window", window=self.windows,
                          live=int(live.sum()), dt_ms=dt_ms)
        return out

    def report(self) -> dict:
        out = {"slots": self.cfg.slots, "live": self.n_live,
               "windows": self.windows,
               "session_carry_bytes": self.session_carry_bytes,
               "fleet_carry_bytes": self.session_carry_bytes
               * self.cfg.slots}
        h = self.obs.registry.histogram("fleet_window_ms")
        if h.count:
            out["window_ms_p50"] = round(h.quantile(0.50), 3)
            out["window_ms_p99"] = round(h.quantile(0.99), 3)
        return out
