"""Stage tracing: the fleet's host spans (admission, slot reset, input
gather, window, bookkeeping, retirement) with parents and self times, the
map from the compiled update chunk's instructions to its named scopes, and
the benchmark's per-stage readers that turn both into per-window times."""
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import cells, sparse_rtrl as SP
from repro.core.cells import EGRUConfig
from repro.core.learner import LearnerSpec, make_learner
from repro.obs import Registry, Telemetry
from repro.obs import trace as trace_mod
from repro.obs.trace import STAGES, Tracer, program_stages
from repro.optim import make_optimizer
from repro.runtime.fleet import FleetConfig, StreamFleet

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import run as R  # noqa: E402
from bench import stages as ST  # noqa: E402

DEVICE_STAGES = ("partials", "j_tile_gather", "mbar_rows",
                 "influence_update", "grad_readout", "optimizer")
DEVICE_READERS = tuple(f"{s}_ms_per_window" for s in DEVICE_STAGES) + (
    "other_chunk_ms_per_window",)
HOST_READERS = {"admission_ms_per_window": "fleet.admit",
                "input_gather_ms_per_window": "fleet.gather",
                "bookkeeping_ms_per_window": "fleet.bookkeep",
                "retire_ms_per_window": "fleet.retire",
                "slot_reset_ms_per_window": "fleet.slot_reset"}
READERS = DEVICE_READERS + tuple(HOST_READERS) + (
    "untraced_idle_ms_per_window",)
SLOTS, B, K = 16, 4, 8


def _stream(step):
    r = np.random.default_rng(step)
    return (r.standard_normal((B, 2)).astype(np.float32),
            (np.arange(B) % 2).astype(np.int32))


def _fleet(tracer):
    """The paper's EGRU (n=16, omega=0.9) in serve.py's learner spec."""
    cfg = EGRUConfig(n_hidden=16, n_in=2, n_out=2, kind="gru")
    masks = SP.make_masks(cfg, jax.random.key(7), 0.9)
    learner = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                       backend="compact", col_compact=True))
    params = SP.apply_masks(cells.init_params(cfg, jax.random.key(0)), masks)
    tel = Telemetry(Registry(), None, tracer, None, "t", None)
    return StreamFleet(FleetConfig(slots=SLOTS, update_every=K), learner,
                       make_optimizer("adamw", lr=5e-3), params, masks,
                       example=_stream(0), telemetry=tel)


def _drive(fleet, windows=3, leave=3):
    """Fill the fleet, then per window: admit into free slots, step, and
    retire the `leave` lowest slots.  Returns (joined, left) sids."""
    joined, left, n = [], [], 0
    for _ in range(windows):
        while fleet.free_slots():
            sid = f"s{n}"
            n += 1
            fleet.add_session(sid, _stream)
            joined.append(sid)
        fleet.step_window()
        for sid in sorted(fleet.sessions,
                          key=lambda s: fleet.sessions[s].slot)[:leave]:
            fleet.remove(sid)
            left.append(sid)
    return joined, left


@pytest.fixture(scope="module")
def traced():
    tr = Tracer(enabled=True)
    fleet = _fleet(tr)
    joined, left = _drive(fleet)
    return tr, fleet, joined, left


def test_fleet_spans_per_window_and_per_session(traced):
    tr, fleet, joined, left = traced
    spans = list(tr.spans)
    names = [s["name"] for s in spans]
    for name in ("fleet.gather", "window", "fleet.bookkeep"):
        assert names.count(name) == fleet.windows == 3, name
    # each window: gather, then dispatch and readback, then bookkeeping
    order = [n for n in names if n in ("fleet.gather", "window",
                                       "fleet.bookkeep")]
    assert order == ["fleet.gather", "window", "fleet.bookkeep"] * 3
    for name, sids in (("fleet.admit", joined), ("fleet.retire", left)):
        mine = [s for s in spans if s["name"] == name]
        assert [s["args"]["sid"] for s in mine] == sids
        assert all(isinstance(s["args"]["slot"], int) for s in mine)
    assert len(joined) == SLOTS + 2 * 3 and len(left) == 3 * 3
    by_id = {s["id"]: s for s in spans}
    children = {}
    for s in spans:
        assert s["dur_ns"] >= 0 and 0 <= s["self_ns"] <= s["dur_ns"]
        if s["parent"] is not None:
            p = by_id[s["parent"]]
            assert s["depth"] == p["depth"] + 1
            assert p["start_ns"] <= s["start_ns"]
            assert s["start_ns"] + s["dur_ns"] <= p["start_ns"] + p["dur_ns"]
            children.setdefault(p["id"], []).append(s)
        else:
            assert s["depth"] == 0
    for s in spans:
        kids = children.get(s["id"], [])
        assert s["self_ns"] == s["dur_ns"] - sum(k["dur_ns"] for k in kids)


def test_fleet_slot_reset_span_per_window_with_pending_slots(traced):
    """One `fleet.slot_reset` span before each window that had pending
    slots, its `slots` the number written, under no other fleet span."""
    tr, fleet, joined, left = traced
    spans = list(tr.spans)
    by_id = {s["id"]: s for s in spans}
    resets = [s for s in spans if s["name"] == "fleet.slot_reset"]
    # window 1: every slot joined; then each window: 3 leave, 3 join there
    assert [s["args"]["slots"] for s in resets] == [SLOTS, 3, 3]
    assert all(type(s["args"]["slots"]) is int for s in resets)
    assert len(resets) == fleet.windows
    assert fleet.obs.registry.counter(
        "fleet_slot_resets_total").value == SLOTS + 3 + 3
    for s in resets:
        p = s["parent"]
        while p is not None:
            assert not by_id[p]["name"].startswith("fleet."), by_id[p]
            p = by_id[p]["parent"]
    # each reset comes right before its window's input gather
    order = [s["name"] for s in spans
             if s["name"] in ("fleet.slot_reset", "fleet.gather")]
    assert order == ["fleet.slot_reset", "fleet.gather"] * 3


def test_fleet_with_tracer_off_records_nothing():
    tr = Tracer(enabled=False)
    fleet = _fleet(tr)
    _drive(fleet, windows=2, leave=2)
    assert len(tr.spans) == 0 and tr.programs == {}
    assert tr.note_program("fleet_chunk", fleet._chunk) is None


_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\([^)]*\)|\S+) "
                 r"(fusion|dot|custom-call|gather)\(")


def test_note_program_maps_the_chunk_to_its_stages(traced):
    tr, fleet, _, _ = traced
    stages = tr.programs["fleet_chunk"]
    assert set(DEVICE_STAGES) <= set(stages.values())
    assert set(stages.values()) <= set(STAGES) | {None}
    # the map is the compiled chunk's: the dispatched program's instructions
    args = (fleet.carry, fleet.opt_state,
            np.zeros((SLOTS, K, B, 2), np.float32),
            np.zeros((SLOTS, K, B), np.int32), np.zeros((SLOTS,), np.int32),
            np.zeros((SLOTS,), bool))
    text = fleet._chunk.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_fleet_chunk")
    ops = [m.group(1) for m in map(_OP.match, text.splitlines()) if m]
    assert ops and set(ops) <= set(stages)
    staged = sum(stages[op] is not None for op in ops)
    assert staged >= 0.9 * len(ops), (staged, len(ops))


def test_program_stages_reads_op_names():
    hlo = "\n".join([
        "HloModule jit_fleet_chunk, entry_computation_layout={()->f32[]}",
        "%fused_computation.3 (param_0: f32[4]) -> f32[4] {",
        '  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(f)/vmap()/while/body/mbar_rows/mul"}',
        "}",
        "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
        '  %dot.2 = f32[4]{0} dot(%p, %p), metadata={op_name="jit(f)/vmap()'
        '/while/body/closed_call/grad_readout/transpose(jvp())/dot_general"}',
        '  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, calls='
        '%fused_computation.3',
        '  %add.5 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/'
        'vmap(optimizer)/telemetry/add"}',
        '  %sub.7 = f32[4]{0} subtract(%p, %p), metadata={op_name="jit(f)/'
        'transpose(jvp(partials))/sub"}',
        '  ROOT %copy.6 = f32[4]{0} copy(%add.5), metadata={op_name="jit(f)'
        '/while"}',
        "}"])
    assert program_stages(hlo) == {"mul.1": "mbar_rows",
                                   "dot.2": "grad_readout",
                                   "fusion.4": None, "add.5": "optimizer",
                                   "sub.7": "partials", "copy.6": None}


# -- the benchmark's readers, on a synthetic trace ---------------------------

MS = 1_000_000


def _read(name, ctx):
    mod = R.load_module(R.BENCH / "metrics" / f"{name}.py", f"m_{name}")
    return mod.read(ctx)


def _synthetic(monkeypatch):
    """A tracer holding a chunk map and the spans of a set-up window and 3
    traced windows, and the trace summary that goes with them: between
    windows 10 ms of bookkeeping, 5 of retirement, 20 of admission, 2 of
    slot reset, 4 of input gather, and 1 ms idle under no span."""
    monkeypatch.setattr(trace_mod, "_current", None)
    tr = Tracer(enabled=True)
    assert trace_mod.current() is tr
    tr.programs["fleet_chunk"] = {
        "fusion.1": "partials", "fusion.2": "j_tile_gather",
        "fusion.3": "mbar_rows", "dot.4": "influence_update",
        "fusion.5": "grad_readout", "fusion.6": "optimizer",
        "fusion.7": "telemetry", "while.8": None, "param.9": None}
    ops = {"fusion.1": 0.3, "fusion.2": 0.6, "fusion.3": 2.4, "dot.4": 0.15,
           "fusion.5": 0.09, "fusion.6": 0.21, "fusion.7": 0.03,
           "while.8": 0.012, "dynamic-update-slice.3": 0.5}
    chunk_s = sum(v for k, v in ops.items() if k in tr.programs["fleet_chunk"])
    t = 0
    for w in range(4):               # window 0 is set-up's, not traced
        for name, dur in (("fleet.admit", 20), ("fleet.slot_reset", 2),
                          ("fleet.gather", 4),
                          ("window", 1000), ("fleet.bookkeep", 10),
                          ("fleet.retire", 5)):
            tr.spans.append({"id": len(tr.spans), "parent": None,
                             "name": name, "start_ns": t, "dur_ns": dur * MS,
                             "self_ns": dur * MS, "depth": 0, "args": {}})
            t += dur * MS
        t += MS                      # under no span
    trace = {"ops": ops, "gaps": {"(no host span)": 2e-3, "window": 0.1},
             "chunk": {"program": "jit_fleet_chunk(1)", "runs": 3,
                       "busy_s": chunk_s, "idle_between_s": 2 * 42e-3}}
    return {"trace": trace, "spec": {"trace_windows": 3}}, chunk_s


def test_device_stage_readers_add_up_to_the_chunk(monkeypatch):
    ctx, chunk_s = _synthetic(monkeypatch)
    got = {name: _read(name, ctx) for name in DEVICE_READERS}
    assert got["mbar_rows_ms_per_window"] == pytest.approx(800.0)
    assert got["other_chunk_ms_per_window"] == pytest.approx(14.0)
    assert sum(got.values()) == pytest.approx(1e3 * chunk_s / 3)
    assert sum(got.values()) == pytest.approx(
        _read("update_chunk_ms_per_window", ctx))


def test_host_readers_add_up_to_the_gap(monkeypatch):
    ctx, _ = _synthetic(monkeypatch)
    got = {name: _read(name, ctx) for name in HOST_READERS}
    assert got == pytest.approx({"admission_ms_per_window": 20.0,
                                 "input_gather_ms_per_window": 4.0,
                                 "bookkeeping_ms_per_window": 10.0,
                                 "retire_ms_per_window": 5.0,
                                 "slot_reset_ms_per_window": 2.0})
    untraced = _read("untraced_idle_ms_per_window", ctx)
    assert untraced == pytest.approx(1.0)
    assert sum(got.values()) + untraced == pytest.approx(
        _read("host_gap_ms_per_window", ctx))


def test_slot_reset_reader_is_none_where_no_reset_span_exists(monkeypatch):
    """A program that opens no `fleet.slot_reset` span (one that resets
    slots one by one) gives no reading rather than 0."""
    ctx, _ = _synthetic(monkeypatch)
    tr = trace_mod.current()
    kept = [s for s in tr.spans if s["name"] != "fleet.slot_reset"]
    tr.spans.clear()
    tr.spans.extend(kept)
    assert _read("admission_ms_per_window", ctx) == pytest.approx(20.0)
    assert _read("slot_reset_ms_per_window", ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_a_tracer_or_a_chunk(monkeypatch, name):
    ctx, _ = _synthetic(monkeypatch)
    assert _read(name, ctx) is not None
    ctx_no_chunk = {**ctx, "trace": {**ctx["trace"], "chunk": None}}
    if name not in HOST_READERS:
        assert _read(name, ctx_no_chunk) is None
    monkeypatch.setattr(trace_mod, "_current", None)
    assert _read(name, ctx) is None
    # a program that keeps no tracer at all
    monkeypatch.delattr(trace_mod, "current")
    assert ST.tracer() is None and _read(name, ctx) is None
