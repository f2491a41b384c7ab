"""granitemoehybrid's period on the online path (`repro.cells.mamba2`): the
top Mamba-2 mixer learned by exact head-diagonal RTRL (`engine=
"diag_exact"`) against a plain jax.numpy reference (`tests/mamba2_ref.py`,
reverse mode through the whole history, every matmul at HIGHEST), at a
small size with the published structure: a period of three layers with one
attention layer, d_model 64, four heads of 8, d_state 16, vocab 256, three
rows, two local heads of the four."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cells import Cell, make_cell, resolve_cell
from repro.cells.mamba2 import (GraniteHybridConfig, Mamba2PeriodCell,
                                init_mixer, init_params, join_top, split_top)
from repro.core.learner import LearnerSpec, make_learner
from repro.optim import make_optimizer
from repro.runtime.online import (OnlineTrainer, OnlineTrainerConfig,
                                  stream_grads)
from repro.runtime.trainer import run_with_restart

_spec = importlib.util.spec_from_file_location(
    "mamba2_ref", Path(__file__).resolve().parent / "mamba2_ref.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

CFG = GraniteHybridConfig(
    hidden_size=64, intermediate_size=128, vocab_size=256,
    layer_types=("mamba", "attention", "mamba"), mamba_n_heads=4,
    mamba_d_head=8, mamba_d_state=16, num_attention_heads=4,
    num_key_value_heads=2, local_heads=(0, 2), kv_slots=32)
B, K = 3, 4
LR, B1, B2 = 1e-3, 0.9, 0.95


def _stream(T=16, seed=0, reset_at=((6, 1),)):
    """tokens, resets, targets [T, B]: row 1 starts a document at step 6
    (inside window 2)."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, CFG.vocab_size, (T + 1, B)).astype(np.int32)
    resets = np.zeros((T, B), np.int32)
    resets[0] = 1
    for t, b in reset_at:
        if t < T:
            resets[t, b] = 1
    return toks[:-1], resets, toks[1:]


def _step_fn(toks, resets, targets):
    def stream(t):
        return (np.stack([toks[t], resets[t]], 1),
                targets[t])
    return stream


def _learner(cfg=CFG):
    return make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))


def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _close(prog, ref, rtol):
    """Every leaf within rtol of the reference, relative to the leaf's
    largest element (elements near zero carry f32 rounding of the
    leaf's scale)."""
    p, r = _leaves(prog), _leaves(ref)
    assert set(p) == set(r)
    for k in r:
        scale = np.max(np.abs(r[k]))
        np.testing.assert_allclose(p[k], r[k], rtol=rtol,
                                   atol=rtol * scale, err_msg=k)


def test_cell_behind_the_protocol():
    cell = make_cell("mamba2", CFG)
    assert isinstance(cell, Cell) and cell.jac_kind == "head_diagonal"
    assert isinstance(resolve_cell(CFG), Mamba2PeriodCell)
    learned, frozen = init_params(CFG, jax.random.key(0))
    assert "out" in learned and "out" not in cell.rec_params(learned)
    lrn = _learner()
    x0, y0 = jnp.zeros((B, 2), jnp.int32), jnp.zeros((B,), jnp.int32)
    carry = lrn.init(learned, None, (x0, y0))        # no frozen weights
    with pytest.raises(ValueError):
        lrn.step(carry, x0, y0)


def test_split_and_join_are_inverse():
    cfg = CFG.replace(local_heads=(1, 3))
    m = init_mixer(cfg, jax.random.key(2), jnp.float32)
    again = join_top(cfg, *split_top(cfg, m))
    for k in m:
        np.testing.assert_array_equal(np.asarray(m[k]), np.asarray(again[k]))


def test_one_window_matches_reverse_mode():
    """(a) One window's loss and every gradient leaf equal the reference's
    reverse mode through the window."""
    learned, frozen = init_params(CFG, jax.random.key(3))
    toks, resets, targets = _stream(T=K)
    lrn = _learner()
    carry = lrn.init(learned, None, (jnp.asarray(np.stack(
        [toks[0], resets[0]], 1)), jnp.asarray(targets[0])),
        t_total=K, frozen=frozen)
    xs = jnp.asarray(np.stack([toks, resets], -1))
    _, loss, grads, stats = jax.jit(
        lambda c: stream_grads(lrn, c, xs, jnp.asarray(targets)))(carry)
    ref_loss, ref_grads, _ = REF.run(CFG, learned, frozen, toks, resets,
                                     targets, K, 1, LR, B1, B2)
    np.testing.assert_allclose(float(loss), float(ref_loss[0]), rtol=1e-5)
    _close(grads, ref_grads[0], 1e-5)
    assert float(np.sum(stats["tokens"])) == K * B


def test_trainer_matches_reference_over_windows_with_a_reset():
    """(b) OnlineTrainer over 4 windows, row 1 starting a new document
    inside window 2: per-window losses and the parameters after each
    window match the reference."""
    learned, frozen = init_params(CFG, jax.random.key(4))
    toks, resets, targets = _stream(T=4 * K)
    opt = make_optimizer("adamw", lr=LR, b1=B1, b2=B2, eps=1e-8)
    t = OnlineTrainer(OnlineTrainerConfig(total_steps=4 * K, update_every=K,
                                          log_every=1),
                      _learner(), opt, learned, None,
                      _step_fn(toks, resets, targets),
                      frozen=jax.tree.map(jnp.copy, frozen))
    t.run()
    ref_loss, _, ref_params = REF.run(CFG, learned, frozen, toks, resets,
                                      targets, K, 4, LR, B1, B2)
    np.testing.assert_allclose([r["loss"] for r in t.metrics],
                               np.asarray(ref_loss), rtol=1e-5)
    changes = jax.tree.map(jnp.subtract, t.carry["params"], learned)
    ref_changes = jax.tree.map(jnp.subtract, ref_params[-1], learned)
    _close(changes, ref_changes, 1e-3)


def test_head_blocks_add_up_to_the_uncut_gradient():
    """(c) The chip share: the two head blocks, run one at a time, give the
    uncut model's gradient, the shared B rows and B conv channels as a
    sum and the per-head rows by concatenation; the readout side is the
    same on every block; the other heads' rows stay as they were."""
    toks, resets, targets = _stream(T=K)
    xs = jnp.asarray(np.stack([toks, resets], -1))

    def grads_of(cfg):
        learned, frozen = init_params(cfg, jax.random.key(5))
        lrn = _learner(cfg)
        carry = lrn.init(learned, None, (xs[0], jnp.asarray(targets[0])),
                         t_total=K, frozen=frozen)
        return jax.jit(lambda c: stream_grads(
            lrn, c, xs, jnp.asarray(targets)))(carry)[2]

    whole = grads_of(CFG.replace(local_heads=(0, 4)))
    g0 = grads_of(CFG.replace(local_heads=(0, 2)))
    g1 = grads_of(CFG.replace(local_heads=(2, 4)))
    for k in ("B", "conv_B", "conv_B_b"):
        np.testing.assert_allclose(g0[k] + g1[k], whole[k], rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(whole[k]).max()))
    for k in ("x", "dt", "conv_x", "conv_x_b", "A_log", "dt_bias"):
        np.testing.assert_allclose(jnp.concatenate([g0[k], g1[k]]), whole[k],
                                   rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(whole[k]).max()))
    _close(g0["out"], whole["out"], 1e-5)
    # a trainer on block (2, 4) leaves heads 0-1's rows as they were
    learned, frozen = init_params(CFG.replace(local_heads=(2, 4)),
                                  jax.random.key(5))
    rest0 = jax.device_get(frozen["layers"][-1]["mixer"])
    opt = make_optimizer("adamw", lr=LR, b1=B1, b2=B2, eps=1e-8)
    t = OnlineTrainer(OnlineTrainerConfig(total_steps=2 * K, update_every=K),
                      _learner(CFG.replace(local_heads=(2, 4))), opt,
                      learned, None, _step_fn(*_stream(T=2 * K)),
                      frozen=frozen)
    t.run()
    after = jax.device_get(t.carry["frozen"]["layers"][-1]["mixer"])
    for k in rest0:
        np.testing.assert_array_equal(np.asarray(after[k]),
                                      np.asarray(rest0[k]))


def test_save_kill_resume_gives_bitwise_traces(tmp_path):
    """(d) A worker that crashes mid-stream and resumes from its last
    checkpoint ends with the same traces, states and parameters, bit for
    bit, as one that never crashed."""
    toks, resets, targets = _stream(T=6 * K, reset_at=((6, 1), (13, 0)))
    opt = make_optimizer("adamw", lr=LR, b1=B1, b2=B2, eps=1e-8)

    def make(root, fail_at):
        def mk(attempt):
            learned, frozen = init_params(CFG, jax.random.key(6))
            return OnlineTrainer(
                OnlineTrainerConfig(total_steps=6 * K, update_every=K,
                                    ckpt_every=1, ckpt_dir=str(root),
                                    fail_at_update=fail_at if attempt == 0
                                    else -1),
                _learner(), opt, learned, None,
                _step_fn(toks, resets, targets), frozen=frozen)
        return mk

    made = []

    def keep(mk):
        def inner(attempt):
            made.append(mk(attempt))
            return made[-1]
        return inner

    out = run_with_restart(keep(make(tmp_path / "a", 3)))
    assert out["restarts"] == 1
    crashed = made[-1].carry
    clean = make(tmp_path / "b", -1)(0)
    clean.run()
    for a, b in zip(jax.tree.leaves(crashed), jax.tree.leaves(clean.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trace_cost_is_linear_in_heads_and_needs_no_trace_sized_increment():
    """The head-diagonal engine's cost (`core/costs.py`): linear in the
    heads a chip holds, one read and one write of each trace, and at the
    benchmark cell's shapes 402.7M floats of big traces a row."""
    from repro.core.costs import head_diag_trace_cost
    one = head_diag_trace_cost(6, 8, 64, 128, 2048, 4)
    two = head_diag_trace_cost(6, 16, 64, 128, 2048, 4)
    assert two["flops"] == 2 * one["flops"]
    assert two["bytes"] == 2 * one["bytes"]
    big = 3 * 8 * 64 * 128 * 2048
    assert big == 402_653_184
    assert one["trace_floats"] == 6 * (big + 8 * 64 * 128 * 12)
    assert one["bytes"] >= 2 * 4 * 6 * big
