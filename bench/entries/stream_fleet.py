"""Many online learners on one chip: `runtime/fleet.py` `StreamFleet`,
drained as `launch/serve.py --fleet` drains its queue.

Before every window, sessions from the queue join free slots
(`add_session`); after it, the sessions the traffic picks leave (`remove`),
the same number after every window for every seed.  The queue never runs
dry, so every slot is live in every window.

Set-up drives the first `check_windows` windows of the same fleet through
the same loop (the first compiles), keeping on the host what the check
compares for a sample of set-up's sessions drawn from the seed: the losses
each read back, the optimizer's first moment of its slot after window 1 and
its slot's parameters after the last.  In the measured span every window's
per-session losses are kept (they come back in the window's one packed
readback anyway); the check replays a sample of the sessions that joined
during the span.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import model as M
from bench.entries import common as C
from bench.traffic import generator as G


class Cell:
    def __init__(self, config: dict, spec: dict, mix: dict, seed: int,
                 traced: bool, workdir):
        self.config = dict(config, seed=seed)
        self.model = config["model"]
        self.spec, self.mix, self.seed = spec, mix, seed
        self.traced, self.workdir = traced, workdir
        self.k = int(self.model["update_every"])
        self.n_check = int(spec["check_windows"])
        self.slots = int(spec["slots"])
        self.sessions = {}    # sid -> {"stream", "joined", "loss"}
        self.next_session = 0
        self.windows_done = 0
        self.packs = []       # per-session MetricPack dicts (traced run)

    def setup(self):
        from repro.runtime.fleet import FleetConfig, StreamFleet

        (learner, opt, params, masks, self.params0,
         self.mask) = C.build_learner(self.config)
        self.queue = G.Traffic(self.mix, self.seed, self.model)
        self.tel = C.make_telemetry(self.traced, self.workdir)
        _, stream0 = self.queue.session(0)
        self.fleet = StreamFleet(
            FleetConfig(slots=self.slots, update_every=self.k), learner,
            opt, params, masks, example=stream0(0), telemetry=self.tel)
        b1 = float(self.model["b1"])
        for w in range(1, self.n_check + 1):
            self._window()
            if w == 1:
                m1 = jax.device_get(self.fleet.opt_state["m"])
                slot1 = dict(self._slot_of)
            if w == self.n_check:
                pn = jax.device_get(self.fleet.carry["params"])
                slotn = dict(self._slot_of)
        # the checked sample of set-up's sessions, drawn from the seed; of
        # those, the sessions that held one slot from window 1 to the last
        # checked have their state compared too
        setup = list(self.sessions)
        take = int(self.spec["check_setup_sessions"])
        if len(setup) > take:
            rng = np.random.default_rng([self.seed, 5])
            setup = [setup[i] for i in
                     sorted(rng.choice(len(setup), take, replace=False))]
        self.setup_sessions = setup
        self.setup_full = {}
        for sid in setup:
            slot = slot1.get(sid)
            if slot is not None and slotn.get(sid) == slot \
                    and self.sessions[sid]["joined"] == 0:
                self.setup_full[sid] = (
                    {k: np.asarray(v[slot], np.float64) / (1.0 - b1)
                     for k, v in M.from_program(m1).items()},
                    {k: np.asarray(v[slot], np.float64)
                     for k, v in M.from_program(pn).items()})

    # -- the drain loop ---------------------------------------------------------

    @property
    def _slot_of(self):
        return {sid: s.slot for sid, s in self.fleet.sessions.items()}

    def _admit(self):
        fleet = self.fleet
        while fleet.free_slots():
            sid, stream = self.queue.session(self.next_session)
            self.next_session += 1
            fleet.add_session(sid, stream)
            self.sessions[sid] = {"stream": stream,
                                  "joined": self.windows_done, "loss": []}

    def _window(self) -> int:
        """Admit, step every slot one window, retire the leavers.
        Returns the number of live sessions stepped."""
        self._admit()
        stats = self.fleet.step_window()
        for sid, st in stats.items():
            if "telemetry" in st:
                self.packs.append(st["telemetry"])
            self.sessions[sid]["loss"].append(st["loss"])
        live = sorted(stats, key=lambda sid: self.fleet.sessions[sid].slot)
        for sid in self.queue.leavers(self.windows_done, live):
            self.fleet.remove(sid)
        self.windows_done += 1
        return len(stats)

    def _run_windows(self, until=None, count=None):
        stamps, live, losses = [], [], []
        first = self.windows_done
        t0 = C.now()
        while True:
            live.append(self._window())
            stamps.append(C.now())
            if (until is not None and stamps[-1] >= until) \
                    or len(stamps) == count:
                break
        for s in self.sessions.values():
            w0 = max(first, s["joined"])
            losses += s["loss"][w0 - s["joined"]:]
        self.span_windows = (first, self.windows_done)
        return dict(C.window_record(stamps, t0, [n * self.k for n in live],
                                    losses), program="fleet_chunk")

    def measure(self, seconds: float) -> dict:
        return self._run_windows(until=C.now() + seconds)

    def trace(self, n: int, tdir) -> dict:
        self.packs = []
        with C.Profile(tdir):
            rec = self._run_windows(count=n)
        rec["telemetry"] = C.mean_pack(self.packs)
        rec.update(C.shape(self.model, self.mask, streams=self.slots))
        return rec

    def free(self):
        if self.tel.events is not None:
            self.tel.events.close()
        self.fleet = None

    # -- what the check compares -----------------------------------------------

    def check_record(self) -> list:
        n_t = self.n_check * self.k
        streams = []
        for sid in self.setup_sessions:
            s = self.sessions[sid]
            g1, pn = self.setup_full.get(sid, (None, None))
            streams.append(self._stream(sid, s, n_t, g1, pn))
        lo, hi = getattr(self, "span_windows", (0, 0))
        joined = [sid for sid, s in self.sessions.items()
                  if lo <= s["joined"] < hi and s["loss"]]
        take = int(self.spec["check_span_sessions"])
        if joined:
            rng = np.random.default_rng([self.seed, 4])
            longest = max(joined, key=lambda sid: len(
                self.sessions[sid]["loss"]))
            rest = [sid for sid in joined if sid != longest]
            pick = [longest] + list(rng.choice(
                rest, size=min(take - 1, len(rest)), replace=False))
            for sid in pick:
                streams.append(self._stream(sid, self.sessions[sid], n_t,
                                            None, None))
        return [{"part": "", "model": self.model, "params0": self.params0,
                 "masks": self.mask, "windows": self.n_check,
                 "streams": streams}]

    def _stream(self, sid, s, n_t, g1, pn):
        xs, ys = G.window_inputs(s["stream"], 0, n_t)
        return {"name": sid, "xs": xs, "ys": ys,
                "loss": s["loss"][:self.n_check], "grad1": g1,
                "params": pn}
