"""The cell zoo (repro.cells): protocol conformance, exact diagonal-RTRL
for RG-LRU vs the BPTT oracle (masked + unmasked, streaming bitwise vs the
scan path), e-prop alignment for the spiking cell, EGRU-through-protocol
bit-identity across backends, OnlineTrainer restart for the new engines,
and the O(n·p) cost claims (closed-form + XLA cost_analysis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cells import CELLS, Cell, make_cell, resolve_cell
from repro.cells import rglru as R
from repro.cells import snn as S
from repro.core import costs, sparse_rtrl as SP, cells as egru_cells
from repro.core.cells import EGRUConfig
from repro.core.learner import LearnerSpec, make_learner, scan_learner


def _cos(a, b):
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


def _tree_allclose(g1, g2, atol=1e-7, rtol=1e-4):
    la, lb = jax.tree.leaves(g1), jax.tree.leaves(g2)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol, rtol=rtol)


# --- protocol ----------------------------------------------------------------

def test_every_cell_satisfies_protocol():
    """Every registry entry satisfies the structural Cell protocol and
    resolve_cell maps its config type back to it."""
    from repro.cells.mamba2 import GraniteHybridConfig
    from repro.core.diag_rtrl import DiagCellConfig
    cfgs = {"egru": EGRUConfig(n_hidden=8, n_in=3, n_out=2, kind="gru"),
            "rglru": R.RGLRUCellConfig(n=8, n_in=3, n_out=2),
            "snn": S.SNNConfig(n=8, n_in=3, n_out=2),
            "diag": DiagCellConfig(n=8, n_in=3, n_out=2),
            "mamba2": GraniteHybridConfig(
                hidden_size=16, intermediate_size=32, vocab_size=32,
                layer_types=("attention", "mamba"), mamba_n_heads=2,
                mamba_d_head=4, mamba_d_state=8, num_attention_heads=2,
                num_key_value_heads=1, local_heads=(0, 1), kv_slots=8)}
    assert set(CELLS) == set(cfgs)
    for name, cfg in cfgs.items():
        cell = make_cell(name, cfg)
        assert isinstance(cell, Cell), name
        assert cell.name == name
        assert cell.jac_kind in ("dense", "diagonal", "head_diagonal"), name
        assert resolve_cell(cfg).__class__ is cell.__class__, name
        params = cell.init_params(jax.random.key(0))
        w = cell.rec_params(params)
        if isinstance(w, dict):
            assert "out" not in w, name        # readout is never recurrent
    with pytest.raises(ValueError):
        make_cell("nope", cfgs["egru"])
    with pytest.raises(ValueError):
        resolve_cell(object())


def test_egru_cell_partials_are_the_moved_originals():
    """repro.core.sparse_rtrl re-exports the EGRU partials from the zoo —
    the same function objects, so every historical consumer is bit-for-bit
    unchanged by construction."""
    from repro.cells import egru as Z
    assert SP.cell_partials is Z.cell_partials
    assert SP.cell_partials_full is Z.cell_partials_full


# --- rgLRU: exact diagonal RTRL ---------------------------------------------

def _rglru_setup(seed=0, n=8, n_in=3, n_out=2, T=7, B=4, sparsity=None):
    cfg = R.RGLRUCellConfig(n=n, n_in=n_in, n_out=n_out)
    params = R.init_params(cfg, jax.random.key(seed))
    masks = None
    if sparsity is not None:
        masks = R.make_masks(cfg, jax.random.key(seed + 7), sparsity)
        params = R.apply_masks(params, masks)
    xs = jax.random.normal(jax.random.key(seed + 1), (T, B, n_in))
    labels = jnp.array([i % n_out for i in range(B)])
    return cfg, params, masks, xs, labels


def test_rglru_mbar_matches_jacrev_diagonal():
    """The closed-form per-step trace increments equal the diagonal slice
    of the one-step Jacobian from autodiff — per-parameter, per-step."""
    cfg, params, _, xs, _ = _rglru_setup()
    B = xs.shape[1]
    h0 = jax.random.normal(jax.random.key(2), (B, cfg.n))
    w = {k: v for k, v in params.items() if k != "out"}
    h_new, hp, adiag, mbar = R.cell_partials(cfg, w, h0, xs[0])
    np.testing.assert_allclose(np.asarray(h_new),
                               np.asarray(R.step(cfg, w, h0, xs[0])),
                               atol=1e-7)
    J = jax.jacrev(lambda ww: R.step(cfg, ww, h0, xs[0]))(w)
    for k in ("Wx", "Wi", "Wa"):
        diag = np.einsum("bkjk->bjk", np.asarray(J[k]))    # [B,n,n_in,n]
        np.testing.assert_allclose(np.asarray(mbar[k]), diag, atol=1e-6)
    diag = np.einsum("bkk->bk", np.asarray(J["lam"]))
    np.testing.assert_allclose(np.asarray(mbar["lam"]), diag, atol=1e-6)
    # diagonal J: dh_new/dh_prev is exactly diag(a)
    Jh = np.asarray(jax.jacrev(lambda h: R.step(cfg, w, h, xs[0]))(h0))
    np.testing.assert_allclose(np.einsum("bkbk->bk", Jh),
                               np.asarray(adiag), atol=1e-6)


@pytest.mark.parametrize("sparsity", [None, 0.5])
def test_rglru_diag_exact_matches_bptt(sparsity):
    """engine='diag_exact' gradients equal the reverse-mode BPTT oracle on
    masked and unmasked streams (the summation ORDER differs — forward
    trace accumulation vs reverse adjoints — so agreement is asserted at
    float32 ulp scale, and bitwise claims live in the streaming-vs-scan
    test below, where the order IS identical)."""
    cfg, params, masks, xs, labels = _rglru_setup(sparsity=sparsity)
    learner = make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))
    loss, grads, _ = scan_learner(learner, params, masks, xs, labels)
    l_ref, g_ref = R.bptt_loss_and_grads(cfg, params, xs, labels)
    np.testing.assert_allclose(float(loss), float(l_ref), atol=1e-6)
    if masks is not None:
        # fixed-mask convention: the oracle's grads at DEAD positions are
        # not meaningful (those weights never train) — compare on the live
        # set, and require the engine's dead grads to be EXACTLY zero
        g_ref = {k: (v * masks[k] if k in masks else v)
                 for k, v in g_ref.items()}
        for k in ("Wx", "Wi", "Wa"):
            dead = np.asarray(masks[k]) == 0.0
            assert np.all(np.asarray(grads[k])[dead] == 0.0), k
    _tree_allclose(g_ref, grads)


def test_rglru_streaming_bitwise_equals_scan():
    """The jitted one-step-at-a-time online path replays the whole-sequence
    scan: the loss bit for bit, every gradient element within one ulp (f32).
    XLA compiles the lone step and the T-step scan as two programs and
    fuses the trace update and its contraction differently in each, so one
    rounding of a sum may land one ulp apart (2 of 8 elements of one leaf
    here); no tighter statement holds across two programs."""
    cfg, params, masks, xs, labels = _rglru_setup(sparsity=0.5)
    T = xs.shape[0]
    learner = make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))
    loss, grads, _ = scan_learner(learner, params, masks, xs, labels)
    step = jax.jit(lambda c, x: learner.step(c, x, labels)[0])
    carry = learner.init(params, masks, (xs[0], labels), t_total=T)
    for t in range(T):
        carry = step(carry, xs[t])
    assert float(carry["loss"]) == float(loss)
    for a, b in zip(jax.tree.leaves(learner.grads(carry)),
                    jax.tree.leaves(grads)):
        np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b),
                                        maxulp=1)


def test_rglru_trace_update_flops_scale_linearly_in_n():
    """cost_analysis: doubling the state width n doubles (not quadruples)
    the jitted trace-update FLOPs — O(n·p) with p = 3·n_in·n + n, and NO
    n² Jacobian factor anywhere in the diagonal engine."""
    from repro.launch.costing import cost_analysis_dict

    def flops_at(n):
        cfg = R.RGLRUCellConfig(n=n, n_in=8, n_out=4)
        params = R.init_params(cfg, jax.random.key(0))
        learner = make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))
        B = 2
        x0 = jnp.zeros((B, cfg.n_in))
        labels = jnp.zeros((B,), jnp.int32)
        carry = learner.init(params, None, (x0, labels), t_total=8)
        compiled = jax.jit(
            lambda c, x: learner.step(c, x, labels)[0]).lower(
                carry, x0).compile()
        return float(cost_analysis_dict(compiled).get("flops", 0.0))

    f1, f2 = flops_at(64), flops_at(128)
    if f1 <= 0.0:
        pytest.skip("XLA cost analysis unavailable on this backend")
    ratio = f2 / f1
    assert ratio < 2.6, f"trace update not linear in n: ratio {ratio:.2f}"


def test_diag_engine_aliases_share_one_implementation():
    """'diag' (historical) and 'diag_exact' name the same engine class, and
    the legacy DiagCellConfig carry keys are preserved."""
    from repro.core.diag_rtrl import DiagCellConfig, init_params
    from repro.core.learner import ENGINES
    assert ENGINES["diag"] is ENGINES["diag_exact"]
    cfg = DiagCellConfig(n=8, n_in=3, n_out=2)
    params = init_params(cfg, jax.random.key(0))
    learner = make_learner(LearnerSpec(engine="diag", cfg=cfg))
    carry = learner.init(params, None,
                         (jnp.zeros((2, 3)), jnp.zeros((2,), jnp.int32)),
                         t_total=4)
    assert {"h", "tr", "gw", "gout"} <= set(carry)
    assert set(carry["gw"]) == {"Wx", "Wa", "lam"}


# --- SNN: e-prop -------------------------------------------------------------

def _snn_setup(seed=0, n=16, n_in=4, n_out=2, T=12, B=4):
    cfg = S.SNNConfig(n=n, n_in=n_in, n_out=n_out)
    params = S.init_params(cfg, jax.random.key(seed))
    xs = 1.5 * jax.random.normal(jax.random.key(seed + 10), (T, B, n_in))
    labels = jnp.array([i % n_out for i in range(B)])
    return cfg, params, xs, labels


@pytest.mark.parametrize("seed", [0, 1])
def test_snn_eprop_aligns_with_surrogate_bptt(seed):
    """engine='eprop' gradients are strongly aligned (cos >= 0.9) with the
    exact surrogate-gradient BPTT oracle for both the input and recurrent
    weights, and EXACT on the readout (which bypasses the approximation)."""
    cfg, params, xs, labels = _snn_setup(seed=seed)
    learner = make_learner(LearnerSpec(engine="eprop", cfg=cfg))
    loss, g, _ = scan_learner(learner, params, None, xs, labels)
    l_ref, g_ref = S.bptt_loss_and_grads(cfg, params, xs, labels)
    # identical forward pass -> identical loss
    np.testing.assert_allclose(float(loss), float(l_ref), atol=1e-6)
    assert _cos(g["W"], g_ref["W"]) >= 0.9
    assert _cos(g["R"], g_ref["R"]) >= 0.9
    _tree_allclose(g_ref["out"], g["out"], atol=1e-6)


def test_snn_eprop_traces_have_the_eprop_structure():
    """Membrane traces are rank-1 (decay alpha is constant); only the
    adaptation traces carry a full [B, j, n] tensor — the structural claim
    `costs.eprop_trace_bytes` prices."""
    cfg, params, xs, _ = _snn_setup()
    B = xs.shape[1]
    tr = S.init_eprop_traces(cfg, B)
    assert tr["v_in"].shape == (B, cfg.n_in)        # rank-1, no n axis
    assert tr["v_rec"].shape == (B, cfg.n)
    assert tr["a_in"].shape == (B, cfg.n_in, cfg.n)  # full only for ALIF
    state = S.init_state(cfg, B)
    w = {k: v for k, v in params.items() if k != "out"}
    state2, tr2, e = S.eprop_step(cfg, w, state, tr, xs[0])
    assert e["W"].shape == (B, cfg.n_in, cfg.n)
    assert e["R"].shape == (B, cfg.n, cfg.n)
    # from rest, the first-step eligibility is psi * eps_v (no adaptation)
    want = np.asarray(state2["psi"])[:, None, :] \
        * np.asarray(tr2["v_in"])[:, :, None]
    np.testing.assert_allclose(np.asarray(e["W"]), want, atol=1e-6)


# --- EGRU through the protocol ----------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "compact", "pallas"])
def test_egru_through_protocol_bit_identical(backend):
    """The engines now dispatch EGRU through the cell protocol; every
    backend still reproduces the legacy whole-sequence function
    bit-for-bit."""
    cfg = EGRUConfig(n_hidden=8, n_in=3, n_out=2, kind="gru")
    params = egru_cells.init_params(cfg, jax.random.key(0))
    masks = SP.make_masks(cfg, jax.random.key(7), 0.5)
    params = SP.apply_masks(params, masks)
    xs = jax.random.normal(jax.random.key(1), (7, 4, 3))
    labels = jnp.array([i % 2 for i in range(4)])
    l_ref, g_ref, _ = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend=backend, interpret=True)
    learner = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                       backend=backend, interpret=True))
    loss, grads, _ = scan_learner(learner, params, masks, xs, labels)
    assert float(loss) == float(l_ref)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- OnlineTrainer restart for a new engine ----------------------------------

def _diag_exact_trainer_factory(tmp_path, fail_at=-1, total_steps=18,
                                update_every=3):
    from repro.optim import make_optimizer
    from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig
    cfg = R.RGLRUCellConfig(n=8, n_in=5, n_out=3)
    learner = make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))
    opt = make_optimizer("adamw", lr=1e-2)

    def stream(step):
        key = jax.random.key(1000 + step % 12)
        x = np.asarray(jax.random.normal(key, (4, cfg.n_in)))
        y = np.asarray(jnp.arange(4) % cfg.n_out, dtype=np.int32)
        return x, y

    def make_trainer(attempt=0):
        params = R.init_params(cfg, jax.random.key(0))
        ocfg = OnlineTrainerConfig(
            total_steps=total_steps, update_every=update_every,
            ckpt_every=2, ckpt_dir=str(tmp_path), log_every=1,
            fail_at_update=fail_at if attempt == 0 else -1)
        return OnlineTrainer(ocfg, learner, opt, params, None, stream)

    return make_trainer


def test_online_trainer_diag_exact_resume_is_exact(tmp_path):
    """Crash at update 4 of 6 mid-stream, restart from the checkpointed
    carry (h + eligibility traces + stream position): final state identical
    to an uninterrupted run."""
    from repro.checkpoint import load_checkpoint
    from repro.runtime.trainer import run_with_restart
    out_a = run_with_restart(
        _diag_exact_trainer_factory(tmp_path / "a", fail_at=4))
    assert out_a["restarts"] == 1
    out_b = run_with_restart(
        _diag_exact_trainer_factory(tmp_path / "b", fail_at=-1))
    assert out_a["final_step"] == out_b["final_step"] == 18
    like = _diag_exact_trainer_factory(tmp_path / "like")()._ckpt_tree()
    ta, _ = load_checkpoint(tmp_path / "a", like)
    tb, _ = load_checkpoint(tmp_path / "b", like)
    for a, b in zip(jax.tree.leaves(ta["carry"]),
                    jax.tree.leaves(tb["carry"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- cost model --------------------------------------------------------------

def test_diag_influence_flops_linear_no_n_squared():
    """The diagonal-engine cost formula: linear in p, scaled by the live
    fraction, and ~n² cheaper than the dense-Jacobian influence update at
    matched sizes."""
    n, n_in = 128, 16
    p = 3 * n_in * n + n
    assert costs.diag_influence_flops(n, p) == 2.0 * p
    assert costs.diag_influence_flops(n, 2 * p) == \
        2 * costs.diag_influence_flops(n, p)
    assert costs.diag_influence_flops(n, p, omega=0.9) == \
        pytest.approx(0.1 * 2.0 * p)
    dense = costs.influence_update_flops(n, p)           # 2 n^2 p
    assert dense / costs.diag_influence_flops(n, p) == n * n


def test_eprop_trace_bytes_formula():
    """Rank-1 membrane bytes + full adaptation bytes; LIF (beta_a=0) drops
    the adaptation tensor entirely."""
    B, n, n_in = 4, 64, 16
    alif = costs.eprop_trace_bytes(B, n, n_in)
    lif = costs.eprop_trace_bytes(B, n, n_in, adaptive=False)
    assert lif == B * (n_in + n) * 4
    assert alif == lif + B * (n_in + n) * n * 4
    assert alif == sum(x.size * 4 for x in jax.tree.leaves(
        S.init_eprop_traces(S.SNNConfig(n=n, n_in=n_in), B)))
