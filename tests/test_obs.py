"""Telemetry plane (repro.obs): the in-jit MetricPack is a PURE OBSERVER
— instrumented chunks are bit-identical to bare ones for the solo and the
vmapped fleet paths, all window scalars cost one packed readback — and the
host-side layers round-trip: schema-versioned JSONL events, fixed-bucket
histogram percentiles pinned against numpy, nested spans with parents and
self times, guard event emission under injected faults, and the benchmark
trajectory aggregator's schema checks."""
import json
import math
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cells, sparse_rtrl as SP
from repro.core.cells import EGRUConfig
from repro.core.learner import LearnerSpec, make_learner
from repro.obs import (KIND_FIELDS, SCHEMA_VERSION, EventLog, Histogram,
                       MetricPack, Registry, SchemaError, Telemetry, Tracer,
                       format_summary, read_events)
from repro.obs import trace as trace_mod
from repro.obs.validate import validate_dir
from repro.optim import make_optimizer
from repro.runtime.fleet import FleetConfig, StreamFleet, fleet_update_chunk
from repro.runtime.guard import (FaultPlan, GuardConfig, StreamGuard,
                                 guarded_update_chunk)
from repro.runtime.online import (OnlineTrainer, OnlineTrainerConfig,
                                  online_update_chunk)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
import trajectory  # noqa: E402


def _setup(backend="compact", col=True, n=8, seed=0):
    cfg = EGRUConfig(n_hidden=n, n_in=3, n_out=2, kind="gru")
    masks = SP.make_masks(cfg, jax.random.key(seed + 7), 0.5)
    learner = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                       backend=backend, interpret=True,
                                       col_compact=col))
    opt = make_optimizer("adamw", lr=1e-2)
    params = SP.apply_masks(cells.init_params(cfg, jax.random.key(seed)),
                            masks)
    return cfg, masks, learner, opt, params


def _window(cfg, k=3, B=4, seed=0):
    key = jax.random.key(100 + seed)
    xs = jax.random.normal(key, (k, B, cfg.n_in))
    ys = jnp.broadcast_to(jnp.arange(B) % cfg.n_out, (k, B)).astype(jnp.int32)
    return xs, ys


def _stream(salt=0, B=4):
    def stream(step):
        key = jax.random.key(1000 + salt * 777 + step % 20)
        x = np.asarray(jax.random.normal(key, (B, 3)))
        y = np.asarray(jnp.arange(B) % 2, dtype=np.int32)
        return x, y
    return stream


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(jax.device_get(x)),
                                      np.asarray(jax.device_get(y)))


# ---------------------------------------------------------------------------
# MetricPack: pure observer, one readback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,col", [("compact", True),
                                         ("compact", False),
                                         ("compact_fused", True)])
def test_packed_solo_chunk_bitwise_equals_bare(backend, col):
    """The acceptance bar, solo: online_update_chunk with a MetricPack
    returns carry/opt-state trees BIT-IDENTICAL to the bare chunk — the
    pack's scalar reductions must not change how XLA compiles the chunk's
    own dataflow."""
    cfg, masks, learner, opt, params = _setup(backend, col)
    xs, ys = _window(cfg)
    carry = learner.init(params, masks, (xs[0], ys[0]), t_total=3.0)
    opt_state = jax.jit(opt.init)(params)
    pack = MetricPack.default()
    c_a, o_a, m_a = jax.jit(lambda c, o: online_update_chunk(
        learner, opt, c, o, xs, ys, jnp.int32(0)))(carry, opt_state)
    c_b, o_b, m_b = jax.jit(lambda c, o: online_update_chunk(
        learner, opt, c, o, xs, ys, jnp.int32(0), pack=pack))(
            carry, opt_state)
    _tree_equal((c_a, o_a), (c_b, o_b))
    # the packed chunk returns ONLY the vector: one readback carries all F
    assert set(m_b) == {"packed"} and m_b["packed"].shape == (
        len(pack.names),)
    pk = pack.unpack(m_b["packed"])
    np.testing.assert_array_equal(np.float32(pk["loss"]),
                                  np.asarray(m_a["loss"]))
    np.testing.assert_array_equal(np.float32(pk["act_sparsity"]),
                                  np.mean(np.asarray(m_a["alpha"],
                                                     np.float32)))


def test_packed_guarded_chunk_bitwise_and_verdict_fields():
    """Guard chunk + pack: same bit-identity, and the pack vector carries
    the verdict scalars (health == 0, clip_factor == 1 at clip=+inf) so
    guard and telemetry share ONE readback."""
    cfg, masks, learner, opt, params = _setup()
    xs, ys = _window(cfg)
    carry = learner.init(params, masks, (xs[0], ys[0]), t_total=3.0)
    opt_state = jax.jit(opt.init)(params)
    pack = MetricPack.default()
    clip = jnp.float32(np.inf)
    c_a, o_a, m_a = jax.jit(lambda c, o: guarded_update_chunk(
        learner, opt, c, o, xs, ys, jnp.int32(0), clip))(carry, opt_state)
    c_b, o_b, m_b = jax.jit(lambda c, o: guarded_update_chunk(
        learner, opt, c, o, xs, ys, jnp.int32(0), clip, pack=pack))(
            carry, opt_state)
    _tree_equal((c_a, o_a), (c_b, o_b))
    pk = pack.unpack(m_b["packed"])
    assert pk["health"] == 0.0 and pk["clip_factor"] == 1.0
    assert pk["grad_norm"] > 0.0 and math.isfinite(pk["grad_norm"])
    np.testing.assert_array_equal(np.float32(pk["loss"]),
                                  np.asarray(m_a["loss"]))


def test_packed_fleet_chunk_bitwise_equals_bare():
    """The acceptance bar, fleet: the vmapped chunk with per-lane pack
    rows is bit-identical to the bare fleet chunk, and the packed [S, 3+F]
    rows agree with the bare [S, 3] verdict columns."""
    cfg, masks, learner, opt, params = _setup()
    k, S = 3, 3
    xs1, ys1 = _window(cfg, k=k)
    xs = jnp.stack([xs1 + 0.1 * s for s in range(S)])
    ys = jnp.broadcast_to(ys1, (S,) + ys1.shape)
    carry = learner.init(params, masks, (xs1[0], ys1[0]), t_total=float(k))
    opt_state = jax.jit(opt.init)(params)
    stack = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.repeat(x[None], S, 0), t))((carry, opt_state))
    upd = jnp.zeros((S,), jnp.int32)
    live = jnp.array([True, True, False])       # one dead don't-care lane
    pack = MetricPack.default()
    c_a, o_a, m_a = jax.jit(lambda c, o: fleet_update_chunk(
        learner, opt, c, o, xs, ys, upd, live))(*stack)
    c_b, o_b, m_b = jax.jit(lambda c, o: fleet_update_chunk(
        learner, opt, c, o, xs, ys, upd, live, pack=pack))(*stack)
    _tree_equal((c_a, o_a), (c_b, o_b))
    pk_a = np.asarray(m_a)                      # [S, 3]
    pk_b = np.asarray(m_b)                      # [S, 3 + F]
    assert pk_b.shape == (S, 3 + len(pack.names))
    np.testing.assert_array_equal(pk_a, pk_b[:, :3])
    # per-lane tails decode to each lane's full metric dict
    m0 = pack.unpack(pk_b[0, 3:])
    assert np.float32(m0["loss"]) == pk_a[0, 1]


def test_pack_nan_marks_inapplicable_fields():
    """Fields with no source in the env pack NaN (the 'not applicable'
    marker the JSONL writer later drops)."""
    pack = MetricPack.default()
    vec = jax.jit(lambda: pack.pack({"loss": jnp.float32(2.5)}))()
    pk = pack.unpack(vec)
    assert pk["loss"] == 2.5
    assert pk["clip_factor"] == 1.0 and pk["health"] == 0.0  # defaults
    for name in ("grad_norm", "act_sparsity", "bwd_sparsity", "overflow",
                 "live_col_frac", "kb_min", "kb_mean", "kb_max"):
        assert math.isnan(pk[name]), name
    with pytest.raises(ValueError, match="fields"):
        pack.unpack(vec[:-1])
    with pytest.raises(ValueError, match="duplicate"):
        MetricPack((("a", None), ("a", None)))
    assert "loss" not in MetricPack.default(exclude=("loss",)).names


# ---------------------------------------------------------------------------
# Trainer + telemetry end-to-end
# ---------------------------------------------------------------------------

def _trainer(learner, opt, params, masks, telemetry=None, guard=None,
             plan=None, total=18, k=3, tmp=None):
    ocfg = OnlineTrainerConfig(total_steps=total, update_every=k,
                               ckpt_every=0, log_every=1,
                               ckpt_dir=str(tmp) if tmp else None)
    return OnlineTrainer(ocfg, learner, opt, params, masks, _stream(),
                         guard=guard, fault_plan=plan, telemetry=telemetry)


def test_trainer_with_telemetry_is_bitwise_identical(tmp_path):
    """Instrumented run (active telemetry -> MetricPack path, one packed
    readback/window) == bare run: same metric records, same final carry
    and optimizer bits; artifacts appear and pass the CI validator."""
    cfg, masks, learner, opt, params = _setup()
    bare = _trainer(learner, opt, params, masks)
    out_a = bare.run()
    obs = Telemetry.create(tmp_path / "m", trace=True, run_id="t0",
                           config={"test": True})
    inst = _trainer(learner, opt, params, masks, telemetry=obs)
    out_b = inst.run()
    _tree_equal(bare.carry, inst.carry)
    _tree_equal(bare.opt_state, inst.opt_state)
    strip = lambda ms: [{k: v for k, v in m.items() if k != "dt_s"}
                        for m in ms]                    # wall clock varies
    assert strip(out_a["metrics"]) == strip(out_b["metrics"])
    obs.finalize(final={"final_loss": out_b["metrics"][-1]["loss"]})
    assert validate_dir(tmp_path / "m") == []
    evs = read_events(tmp_path / "m" / "events.jsonl")
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    wins = [e for e in evs if e["kind"] == "window"]
    assert len(wins) == out_b["updates"]
    # every window event carries the full packed catalog for this engine
    for w in wins:
        for f in ("loss", "grad_norm", "act_sparsity", "bwd_sparsity",
                  "kb_min", "kb_mean", "kb_max", "dt_ms"):
            assert isinstance(w[f], (int, float)), f
    spans = [s for s in obs.tracer.spans if s["name"] == "window"]
    assert len(spans) == out_b["updates"]
    assert all(s["parent"] is None and s["dur_ns"] > 0 for s in spans)
    # --trace records a profiler trace, not a span export of its own
    assert not (tmp_path / "m" / "trace.json").exists()
    assert list((tmp_path / "m" / "profile").glob(
        "plugins/profile/*/*.xplane.pb"))
    man = json.loads((tmp_path / "m" / "manifest.json").read_text())
    assert man["run_id"] == "t0" and man["config"]["test"] is True
    assert man["metrics"]["loss"] == wins[-1]["loss"]
    prom = (tmp_path / "m" / "metrics.prom").read_text()
    assert "# TYPE windows_total counter" in prom
    assert "window_ms_bucket" in prom


def test_guard_events_under_fault_plan(tmp_path):
    """A corrupted carry under the guard emits the contracted JSONL events
    — fault, rollback, recovery — and the guard report's counts source
    from the same registry the events incremented."""
    cfg, masks, learner, opt, params = _setup()
    obs = Telemetry.create(tmp_path / "m")
    t = _trainer(learner, opt, params, masks, telemetry=obs,
                 guard=GuardConfig(),
                 plan=FaultPlan(corrupt_carry_at_update=4),
                 total=30, tmp=tmp_path / "ck")
    out = t.run()
    obs.finalize()
    assert out["guard"]["faults"] == 1 and out["guard"]["rollbacks"] == 1
    evs = read_events(tmp_path / "m" / "events.jsonl")
    by = {}
    for e in evs:
        by.setdefault(e["kind"], []).append(e)
    assert len(by["fault"]) == 1
    assert by["fault"][0]["reason"].startswith("nonfinite")
    assert len(by["rollback"]) == 1
    assert by["rollback"][0]["to_step"] == by["recovery"][0]["step"]
    assert by["recovery"][0]["action"] == "replay"
    reg = obs.registry
    assert reg.counter("guard_faults_total").value == 1
    assert reg.counter("guard_rollbacks_total").value == 1


def test_fleet_session_lifecycle_events(tmp_path):
    """Fleet with active telemetry: join/evict/resume/leave each emit
    their event, step_window returns the decoded per-session telemetry
    tail, and no registry series is labelled by session id."""
    cfg, masks, learner, opt, params = _setup()
    obs = Telemetry.create(tmp_path / "m")
    fleet = StreamFleet(FleetConfig(slots=2, update_every=2,
                                    store_dir=str(tmp_path / "store")),
                        learner, opt, params, masks,
                        example=_stream()(0), telemetry=obs)
    fleet.add_session("a", _stream(1), params=params)
    fleet.add_session("b", _stream(2), params=params)
    stats = fleet.step_window()
    assert "telemetry" in stats["a"]
    assert stats["a"]["telemetry"]["loss"] == stats["a"]["loss"]
    fleet.evict("a")
    fleet.resume("a", _stream(1))
    stats2 = fleet.step_window()
    fleet.remove("b")
    obs.finalize()
    evs = read_events(tmp_path / "m" / "events.jsonl")
    kinds = [e["kind"] for e in evs]
    for k in ("session_join", "session_evict", "session_resume",
              "session_leave", "fleet_window"):
        assert k in kinds, k
    reg = obs.registry
    assert reg.counter("sessions_joined_total").value == 2
    assert reg.counter("sessions_evicted_total").value == 1
    assert reg.counter("sessions_resumed_total").value == 1
    assert stats2["a"]["telemetry"]["loss"] == stats2["a"]["loss"]
    assert stats2["a"]["pos"] == 4 and stats2["a"]["upd"] == 2
    assert "sid=" not in reg.to_prometheus()
    rep = fleet.report()
    assert rep["window_ms_p50"] > 0 and rep["window_ms_p99"] > 0


# ---------------------------------------------------------------------------
# Host-side layers: events, registry, tracer, summary
# ---------------------------------------------------------------------------

def test_event_log_round_trip_and_schema(tmp_path):
    log = EventLog(tmp_path / "e.jsonl")
    log.emit("run_start", run_id="r1")
    log.emit("window", update=1, step=3, dt_ms=2.5,
             loss=np.float32(1.25), overflow=float("nan"))
    log.emit("rewire", event=1, frac=0.2, ms=3.0)
    log.close()
    evs = read_events(tmp_path / "e.jsonl")       # validates every record
    assert [e["kind"] for e in evs] == ["run_start", "window", "rewire"]
    assert all(e["v"] == SCHEMA_VERSION for e in evs)
    assert evs[1]["loss"] == 1.25                 # numpy scalar unwrapped
    assert evs[1]["overflow"] is None             # NaN -> null, strict JSON
    # the file itself is strict JSON per line (no NaN literals)
    for line in (tmp_path / "e.jsonl").read_text().splitlines():
        json.loads(line, parse_constant=lambda c: pytest.fail(c))

    log2 = EventLog(tmp_path / "e2.jsonl")
    with pytest.raises(SchemaError, match="unknown event kind"):
        log2.emit("nope")
    with pytest.raises(SchemaError, match="missing fields"):
        log2.emit("window", update=1)             # step/dt_ms required
    log2.close()
    assert log2.written == 0
    (tmp_path / "bad.jsonl").write_text('{"v": 999, "kind": "window", '
                                        '"ts": 0}\n')
    with pytest.raises(SchemaError, match="schema version"):
        read_events(tmp_path / "bad.jsonl")
    # every contracted kind is emittable with its required fields
    for kind, fields in KIND_FIELDS.items():
        log3 = EventLog(tmp_path / "k.jsonl")
        log3.emit(kind, **{f: 1 for f in fields})
        log3.close()


def test_histogram_percentiles_vs_numpy():
    """Interpolated fixed-bucket quantiles land within one bucket width of
    numpy's exact sample percentiles — the estimator's error bound."""
    rng = np.random.default_rng(3)
    samples = rng.lognormal(mean=1.0, sigma=0.8, size=5000)
    edges = [0.1 * 1.3 ** i for i in range(40)]
    h = Histogram(edges)
    for s in samples:
        h.observe(s)
    full = [0.0] + list(edges) + [float(samples.max())]
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(samples, q * 100))
        est = h.quantile(q)
        i = int(np.searchsorted(edges, exact))
        width = full[i + 1] - full[i]
        assert abs(est - exact) <= width, (q, est, exact, width)
    assert h.count == 5000 and h.min == samples.min()
    # q=1.0 lands on the containing bucket's upper edge — bounded above
    # the true max by at most that bucket's width
    i = int(np.searchsorted(edges, samples.max()))
    assert samples.max() <= h.quantile(1.0) <= full[i + 1] + 1e-9
    assert math.isnan(Histogram(edges).quantile(0.5))    # empty
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram([1.0, 1.0])
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)


def test_registry_semantics_and_prometheus():
    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    assert reg.counter("c").value == 3                   # get-or-create
    with pytest.raises(ValueError, match=">= 0"):
        reg.counter("c").inc(-1)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c")
    reg.gauge("g").set(1.5)
    reg.gauge("s", sid="u1").set(2.0)
    reg.gauge("s", sid="u2").set(3.0)
    h = reg.histogram("h", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap["c"] == 3 and snap["g"] == 1.5
    assert snap['s{sid="u1"}'] == 2.0 and snap['s{sid="u2"}'] == 3.0
    assert snap["h"]["count"] == 2 and snap["h"]["sum"] == 5.5
    prom = reg.to_prometheus()
    assert "# TYPE c counter" in prom and "c 3" in prom
    assert '# TYPE h histogram' in prom
    assert 'h_bucket{le="1"} 1' in prom                  # cumulative
    assert 'h_bucket{le="10"} 2' in prom
    assert 'h_bucket{le="+Inf"} 2' in prom
    assert "h_count 2" in prom
    assert 's{sid="u1"} 2' in prom


def test_tracer_nesting_and_chrome_export(tmp_path):
    """The span record: parent ids, self time (duration less the children),
    starts on the profiler's clock (`time.time_ns`), depth and arguments;
    a tracer that is on is the process's tracer; one that is off records
    nothing."""
    before = time.time_ns()
    tr = Tracer(enabled=True)
    assert trace_mod.current() is tr
    with tr.span("window", update=0):
        with tr.span("rewire", frac=np.float32(0.2)):
            time.sleep(0.002)
        with tr.span("ckpt_write"):
            pass
    assert [s["name"] for s in tr.spans] == ["rewire", "ckpt_write",
                                             "window"]
    by = {s["name"]: s for s in tr.spans}
    win = by["window"]
    assert win["depth"] == 0 and win["parent"] is None
    assert by["rewire"]["depth"] == 1 and by["ckpt_write"]["depth"] == 1
    assert by["rewire"]["parent"] == by["ckpt_write"]["parent"] == win["id"]
    assert by["rewire"]["args"] == {"frac": pytest.approx(0.2)}
    assert before <= win["start_ns"] <= time.time_ns()
    # interval containment: children nest inside the parent
    for child in ("rewire", "ckpt_write"):
        c = by[child]
        assert win["start_ns"] <= c["start_ns"]
        assert (c["start_ns"] + c["dur_ns"]
                <= win["start_ns"] + win["dur_ns"])
        assert c["self_ns"] == c["dur_ns"]          # leaves
    assert by["rewire"]["dur_ns"] >= 2_000_000
    assert win["self_ns"] == (win["dur_ns"] - by["rewire"]["dur_ns"]
                              - by["ckpt_write"]["dur_ns"])
    assert not hasattr(tr, "export_chrome")

    off = Tracer(enabled=False)
    with off.span("window"):
        pass
    assert len(off.spans) == 0 and trace_mod.current() is tr


def test_null_telemetry_is_inert_but_counts(tmp_path):
    obs = Telemetry.null()
    assert not obs.active
    assert obs.emit("window", update=0, step=0, dt_ms=1.0) is None
    with obs.span("window"):
        pass
    obs.record_window(1, 3, 2.0, packed={"loss": 0.5})
    assert obs.registry.counter("windows_total").value == 1
    assert obs.registry.gauge("loss").value == 0.5
    assert obs.finalize() is None
    assert list(tmp_path.iterdir()) == []        # wrote nothing anywhere


def test_format_summary_shape():
    txt = format_summary("t", {"loss": 0.123456789, "updates": 6,
                               "skipme": 1, "guard": {"faults": 0},
                               "flag": None}, skip=("skipme",))
    assert txt.startswith("== t ==")
    assert "skipme" not in txt
    assert "loss" in txt and "0.123457" in txt
    assert "updates" in txt and " 6" in txt
    assert "guard" in txt and "faults" in txt
    assert "flag" in txt and "-" in txt


# ---------------------------------------------------------------------------
# Trajectory aggregator schema
# ---------------------------------------------------------------------------

def _minimal_records(root: Path):
    (root / "BENCH_kernels.json").write_text(json.dumps({
        "compact_sweep": [{"speedup_dual_over_row": 2.0}],
        "fused_sweep": [{"speedup_fused_over_dual": 1.5}],
        "online_step": [{"variant": "compact-dual", "per_step_ms": 1.0}],
        "rewire": [{"amortized_overhead": 0.01}],
        "guard_overhead": {"overhead": 0.02},
        "obs_overhead": {"overhead": 0.01},
        "cell_zoo": []}))
    (root / "BENCH_fleet.json").write_text(json.dumps({
        "sweep": [{"S": 8, "speedup_fleet_over_seq": 5.0,
                   "step_latency_p99_ms": 0.5}]}))
    (root / "BENCH_roofline.json").write_text(json.dumps({
        "peaks": {}, "points": [1, 2]}))


def test_trajectory_aggregate_and_headlines(tmp_path):
    _minimal_records(tmp_path)
    rows = []
    traj = trajectory.run(rows, root=tmp_path)
    assert sorted(traj["files"]) == ["BENCH_fleet.json",
                                     "BENCH_kernels.json",
                                     "BENCH_roofline.json"]
    h = traj["headline"]
    assert h["kernels/obs_overhead"] == 0.01
    assert h["kernels/guard_overhead"] == 0.02
    assert h["fleet/speedup_at_max_S"] == 5.0
    assert h["roofline/points"] == 2
    out = json.loads((tmp_path / "BENCH_trajectory.json").read_text())
    assert trajectory.validate_trajectory(out) == []
    assert out["schema_version"] == trajectory.SCHEMA_VERSION
    # re-aggregation skips its own output and is byte-deterministic
    again = trajectory.run([], root=tmp_path)
    assert "BENCH_trajectory.json" not in again["files"]


def test_trajectory_schema_check_rejects_holes(tmp_path):
    _minimal_records(tmp_path)
    rec = json.loads((tmp_path / "BENCH_kernels.json").read_text())
    del rec["obs_overhead"]
    (tmp_path / "BENCH_kernels.json").write_text(json.dumps(rec))
    with pytest.raises(trajectory.TrajectorySchemaError,
                       match="obs_overhead"):
        trajectory.aggregate(tmp_path)
    assert trajectory.check_record("BENCH_fleet.json", {"sweep": {}}) != []
    assert trajectory.check_record("BENCH_fleet.json", []) != []
    assert trajectory.check_record("BENCH_custom.json", {"x": 1}) == []
    # ci records share the stem's schema
    assert trajectory.check_record("BENCH_fleet.ci.json", {}) != []
    bad = {"schema_version": 999, "headline": {}, "files": {}}
    assert trajectory.validate_trajectory(bad) != []


def test_committed_trajectory_matches_repo_records():
    """The committed BENCH_trajectory.json validates and mirrors the
    committed record files byte-for-value."""
    root = Path(__file__).resolve().parents[1]
    traj = json.loads((root / "BENCH_trajectory.json").read_text())
    assert trajectory.validate_trajectory(traj) == []
    for name, data in traj["files"].items():
        assert json.loads((root / name).read_text()) == data
    assert 0 <= traj["headline"]["kernels/obs_overhead"] < 0.05
