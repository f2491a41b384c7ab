"""Stream fleet: a fleet of 1 is bit-identical to the solo OnlineTrainer,
slots join/leave mid-flight without perturbing their neighbours' bits, and
evict -> resume through the session store round-trips exactly."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointError, list_sessions, load_session,
                              save_session)
from repro.core import cells, sparse_rtrl as SP
from repro.core.cells import EGRUConfig
from repro.core.learner import LearnerSpec, make_learner
from repro.optim import make_optimizer
from repro.runtime.fleet import FleetConfig, StreamFleet, fleet_update_chunk
from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig


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


@pytest.mark.parametrize("backend,col", [("compact", True),
                                         ("compact", False),
                                         ("compact_fused", True)])
def test_fleet_of_one_bitwise_equals_solo(backend, col):
    """The acceptance bar: S=1 fleet == solo OnlineTrainer, every carry and
    optimizer leaf bit-for-bit, after 8 update windows."""
    cfg, masks, learner, opt, params = _setup(backend, col)
    stream = _stream()
    tr = OnlineTrainer(OnlineTrainerConfig(total_steps=24, update_every=3,
                                           ckpt_every=0, log_every=100),
                       learner, opt, params, masks, stream)
    tr.run()

    fleet = StreamFleet(FleetConfig(slots=1, update_every=3), learner, opt,
                        params, masks, example=stream(0))
    fleet.add_session("u0", stream, params=params)
    for _ in range(8):
        stats = fleet.step_window()
    carry_f, opt_f = fleet.slot_state("u0")
    _tree_equal(tr.carry, carry_f)
    _tree_equal(tr.opt_state, opt_f)
    assert stats["u0"]["pos"] == 24 and stats["u0"]["upd"] == 8


def test_join_leave_mid_flight_leaves_neighbours_bit_identical():
    """A session joining at window 2 and leaving at window 5 must not move
    a single bit of any other slot — continuous batching is lane-exact."""
    cfg, masks, learner, opt, params = _setup()
    streams = {f"u{i}": _stream(salt=i) for i in range(3)}

    def run(with_guest):
        fleet = StreamFleet(FleetConfig(slots=4, update_every=2), learner,
                            opt, params, masks, example=streams["u0"](0))
        for sid in streams:
            fleet.add_session(sid, streams[sid], params=params)
        for w in range(8):
            if with_guest and w == 2:
                fleet.add_session("guest", _stream(salt=99), params=params)
            if with_guest and w == 5:
                fleet.remove("guest")
            fleet.step_window()
        return {sid: fleet.slot_state(sid) for sid in streams}

    alone = run(with_guest=False)
    shared = run(with_guest=True)
    for sid in streams:
        _tree_equal(alone[sid], shared[sid])


def test_evict_resume_roundtrip_bitwise(tmp_path):
    """Evict a session to the store mid-stream, run other traffic, resume
    into a DIFFERENT slot: end state equals the never-evicted run exactly."""
    cfg, masks, learner, opt, params = _setup()
    stream = _stream(salt=3)

    def run(evict):
        fleet = StreamFleet(FleetConfig(slots=2, update_every=2,
                                        store_dir=str(tmp_path / "store")),
                            learner, opt, params, masks, example=stream(0))
        fleet.add_session("a", stream, params=params)
        for w in range(3):
            fleet.step_window()
        if evict:
            pos = fleet.evict("a")
            assert pos == 6
            assert list_sessions(str(tmp_path / "store")) == ["a"]
            # unrelated traffic while "a" is parked
            fleet.add_session("filler", _stream(salt=8), params=params)
            fleet.step_window()
            fleet.resume("a", stream)
            fleet.remove("filler")
        for w in range(3):
            fleet.step_window()
        return fleet.slot_state("a"), fleet.sessions["a"]

    (c_ref, o_ref), _ = run(evict=False)
    (c_ev, o_ev), sess = run(evict=True)
    _tree_equal(c_ref, c_ev)
    _tree_equal(o_ref, o_ev)
    assert sess.pos == 12 and sess.upd == 6


def test_dead_slots_emit_no_stats_and_cost_no_bookkeeping():
    """Dead slots never appear in window stats, and the packed readback
    masks their rows to live=0."""
    cfg, masks, learner, opt, params = _setup()
    fleet = StreamFleet(FleetConfig(slots=4, update_every=2), learner, opt,
                        params, masks, example=_stream()(0))
    fleet.add_session("only", _stream(), params=params)
    stats = fleet.step_window()
    assert set(stats) == {"only"}
    assert np.isfinite(stats["only"]["loss"])
    xs, ys, upd, live = fleet._gather(2)
    assert live.tolist() == [True, False, False, False]
    packed = jax.jit(
        lambda c, o, x, y, u, l: fleet_update_chunk(
            fleet.learner, fleet.opt, c, o, x, y, u, l)[2])(
        fleet.carry, fleet.opt_state, jnp.asarray(xs), jnp.asarray(ys),
        jnp.asarray(upd), jnp.asarray(live))
    pk = np.asarray(packed)
    assert pk[0, 0] == 1.0 and (pk[1:, 0] == 0.0).all()


def test_slot_exhaustion_and_duplicate_sid_raise():
    cfg, masks, learner, opt, params = _setup()
    fleet = StreamFleet(FleetConfig(slots=1, update_every=2), learner, opt,
                        params, masks, example=_stream()(0))
    fleet.add_session("a", _stream(), params=params)
    with pytest.raises(ValueError, match="already"):
        fleet.add_session("a", _stream())
    with pytest.raises(ValueError, match="full"):
        fleet.add_session("b", _stream())
    fleet.remove("a")
    assert fleet.n_live == 0
    fleet.add_session("b", _stream())
    assert fleet.n_live == 1


def test_session_store_namespacing_and_validation(tmp_path):
    """save_session namespaces under session/<sid>; hostile sids are
    rejected; a corrupted payload falls back per the PR-6 validation."""
    tree = {"w": jnp.arange(4, dtype=jnp.float32)}
    p = save_session(str(tmp_path), "user-1", tree, step=2)
    assert "session/user-1" in str(p).replace("\\", "/")
    got, step = load_session(str(tmp_path), "user-1", tree)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(4))

    for bad in ("../evil", "a/b", "", "x y"):
        with pytest.raises(ValueError):
            save_session(str(tmp_path), bad, tree)

    with pytest.raises(CheckpointError):
        load_session(str(tmp_path), "never-saved", tree)
    assert list_sessions(str(tmp_path)) == ["user-1"]


# -- template joins and leaves: one batched slot reset per window -----------

def _same_arrays(a, b):
    return all(x is y for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _counting(fleet, name):
    """Wrap the fleet's jitted program `name` to count its dispatches."""
    calls = []
    jitted = getattr(fleet, name)

    def wrapped(*args):
        calls.append(1)
        return jitted(*args)
    setattr(fleet, name, wrapped)
    return calls


def test_template_joins_and_leaves_make_one_reset_per_window():
    """Joins from the template and leaves touch no device buffer; the next
    window makes exactly one `jit_slot_reset` dispatch for all of them, and
    `fleet_slot_resets_total` counts the distinct slots it wrote."""
    cfg, masks, learner, opt, params = _setup()
    fleet = StreamFleet(FleetConfig(slots=4, update_every=2), learner, opt,
                        params, masks, example=_stream()(0))
    mask = np.zeros((4,), bool)
    text = fleet._reset.lower(fleet._carry, fleet._opt_state, mask,
                              fleet._template).as_text()
    assert "@jit_slot_reset" in text
    resets = _counting(fleet, "_reset")
    writes = _counting(fleet, "_write")
    counter = fleet.obs.registry.counter("fleet_slot_resets_total")
    stacks = (fleet._carry, fleet._opt_state)
    for i in range(3):
        fleet.add_session(f"a{i}", _stream(salt=i))
    # no device work on admission: the stacks are the same arrays
    assert _same_arrays((fleet._carry, fleet._opt_state), stacks)
    fleet.step_window()
    assert len(resets) == 1 and counter.value == 3
    fleet.remove("a0")
    fleet.remove("a1")
    stacks = (fleet._carry, fleet._opt_state)
    fleet.add_session("b0", _stream(salt=5))    # slot 0, already pending
    fleet.add_session("b1", _stream(salt=6))    # slot 1
    fleet.add_session("b2", _stream(salt=7))    # slot 3
    assert _same_arrays((fleet._carry, fleet._opt_state), stacks)
    fleet.step_window()
    assert len(resets) == 2 and counter.value == 3 + 3
    fleet.step_window()                         # nothing pending
    assert len(resets) == 2 and counter.value == 6
    assert writes == []


def test_template_joins_equal_explicit_joins_bitwise():
    """Sessions joining from the template (reset in one batch per window)
    end bit-identical, slot by slot, to the same sessions joining with
    `params=` the template's parameters (written slot by slot at once)."""
    cfg, masks, learner, opt, params = _setup()

    def run(explicit):
        fleet = StreamFleet(FleetConfig(slots=4, update_every=2), learner,
                            opt, params, masks, example=_stream()(0))
        kw = {"params": params} if explicit else {}
        for i in range(3):
            fleet.add_session(f"u{i}", _stream(salt=i), **kw)
        for w in range(6):
            if w == 2:
                fleet.remove("u1")
                fleet.add_session("g0", _stream(salt=10), **kw)
                fleet.add_session("g1", _stream(salt=11), **kw)
            if w == 4:
                fleet.remove("u0")
                fleet.remove("g1")
                fleet.add_session("g2", _stream(salt=12), **kw)
            fleet.step_window()
        fleet.remove("g0")
        return fleet, {sid: fleet.slot_state(sid) for sid in fleet.sessions}

    f_t, per_t = run(explicit=False)
    f_e, per_e = run(explicit=True)
    assert sorted(per_t) == sorted(per_e) == ["g2", "u2"]
    for sid in per_t:
        assert f_t.sessions[sid].slot == f_e.sessions[sid].slot
        _tree_equal(per_t[sid], per_e[sid])
    _tree_equal((f_t.carry, f_t.opt_state), (f_e.carry, f_e.opt_state))


def test_reads_before_the_next_window_see_the_template():
    """`slot_state` and `fleet.carry` read between a join or leave and the
    next window show the template in the pending slots."""
    cfg, masks, learner, opt, params = _setup()
    fleet = StreamFleet(FleetConfig(slots=3, update_every=2), learner, opt,
                        params, masks, example=_stream()(0))
    t_carry, t_opt = fleet._template
    fleet.add_session("a", _stream(salt=1))
    fleet.add_session("b", _stream(salt=2))
    _tree_equal(fleet.slot_state("a"), (t_carry, t_opt))
    for _ in range(2):
        fleet.step_window()
    moved = fleet.slot_state("a")[0]["params"]
    assert any((np.asarray(x) != np.asarray(y)).any() for x, y in zip(
        jax.tree.leaves(moved), jax.tree.leaves(t_carry["params"])))
    fleet.remove("a")                           # slot 0 pending
    assert fleet._pending == {0}
    carry, opt_state = fleet.carry, fleet.opt_state
    assert not fleet._pending
    _tree_equal(jax.tree.map(lambda x: x[0], (carry, opt_state)),
                (t_carry, t_opt))
    fleet.remove("b")                           # slot 1 pending
    fleet.add_session("c", _stream(salt=3))     # slot 0
    assert fleet.sessions["c"].slot == 0 and fleet._pending == {0, 1}
    _tree_equal(fleet.slot_state("c"), (t_carry, t_opt))
    _tree_equal(jax.tree.map(lambda x: x[1], fleet.opt_state), t_opt)


def test_resume_into_a_pending_slot_keeps_its_loaded_state(tmp_path):
    """A session resumed into a slot whose template reset is still pending
    keeps its loaded state bitwise, through the next window too."""
    cfg, masks, learner, opt, params = _setup()
    stream = _stream(salt=4)

    def fleet_with_a():
        fleet = StreamFleet(FleetConfig(slots=2, update_every=2,
                                        store_dir=str(tmp_path / "store")),
                            learner, opt, params, masks, example=stream(0))
        fleet.add_session("a", stream)
        for _ in range(2):
            fleet.step_window()
        return fleet

    ref = fleet_with_a()
    fleet = fleet_with_a()
    before = fleet.slot_state("a")
    fleet.evict("a")                            # slot 0 pending
    assert fleet._pending == {0}
    assert fleet.resume("a", stream) == 0
    assert not fleet._pending
    _tree_equal(fleet.slot_state("a"), before)
    for f in (ref, fleet):
        f.step_window()
    _tree_equal(fleet.slot_state("a"), ref.slot_state("a"))


_SLICES = re.compile(r"slice_sizes=\{([0-9,]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_fleet_chunk_looks_up_tiles_without_element_gathers():
    """The vmapped chunk of 4 slots of the n=16 EGRU (compact, col_compact)
    looks its J-hat tiles up by compare-and-select: no `gather` under the
    `j_tile_gather` scope takes one element per index (all slice sizes 1),
    the op that took 87% of the fleet's window on a TPU v5e."""
    cfg, masks, learner, opt, params = _setup("compact", True, n=16)
    fleet = StreamFleet(FleetConfig(slots=4, update_every=2), learner, opt,
                        params, masks, example=_stream()(0))
    args = [jnp.asarray(a) for a in fleet._gather(2)]
    hlo = fleet._chunk.lower(fleet.carry, fleet.opt_state,
                             *args).compile().as_text()
    scoped = [line for line in hlo.splitlines()
              if "j_tile_gather" in (_OP_NAME.search(line) or [""])[0]]
    assert any("j_tile_gather/tile_select" in line for line in scoped)
    elementwise = [line for line in scoped if " gather(" in line
                   and set(_SLICES.search(line).group(1).split(",")) == {"1"}]
    assert not elementwise, elementwise
