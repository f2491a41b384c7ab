"""Fused dual-compact influence kernel (kernels/compact_fused.py).

Three layers of pinning:
  1. kernel-level: interpret-mode `fused_update_pallas` vs the pure-jnp
     `fused_reference` — BITWISE for an f32 carry (same blockwise f32
     accumulation order), bounded for bf16 — over ragged heterogeneous
     batches with dead-slot sentinels;
  2. engine-level: backend="compact_fused" (XLA lowering and the Pallas
     interpret path) vs backend="compact" and the masked-dense oracle,
     single-layer / stacked / scaled, both carry dtypes, dual ColLayouts;
  3. contract-level: segment-table validation, overflow reporting, the
     rewirable / dense-bf16 / col_compact=False rejections.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cells, scaled_rtrl as SC, sparse_rtrl as SP, \
    stacked_rtrl as ST
from repro.core.cells import EGRUConfig
from repro.core.learner import LearnerSpec, make_learner
from repro.kernels import compact as CK
from repro.kernels import compact_fused as CF


# ---------------------------------------------------------------------------
# 1. kernel level: interpret Pallas vs fused_reference on synthetic raggedness
# ---------------------------------------------------------------------------

def _ragged_inputs(seed, B=3, K=16, n=40, Pc_pad=128, dtype=jnp.float32):
    """Synthetic fused-update operands honouring the carry contract: indices
    -1-sentineled past each example's count, dead vals/hp slots exactly 0,
    per-example counts deliberately heterogeneous (the ragged case)."""
    rng = np.random.default_rng(seed)
    count_new = rng.integers(1, K + 1, B).astype(np.int32)
    count_prev = rng.integers(1, K + 1, B).astype(np.int32)
    count_new[0], count_prev[0] = K, K          # one full example
    count_new[1] = 1                            # one nearly-empty example
    idx_new = np.full((B, K), -1, np.int32)
    idx_prev = np.full((B, K), -1, np.int32)
    for b in range(B):
        idx_new[b, :count_new[b]] = np.sort(
            rng.choice(n, count_new[b], replace=False))
        idx_prev[b, :count_prev[b]] = np.sort(
            rng.choice(n, count_prev[b], replace=False))
    Jhat = rng.normal(size=(B, n, n)).astype(np.float32)
    vals = rng.normal(size=(B, K, Pc_pad)).astype(np.float32)
    vals[idx_prev < 0] = 0.0
    mbar = rng.normal(size=(B, K, Pc_pad)).astype(np.float32)
    hp = np.abs(rng.normal(size=(B, K))).astype(np.float32)
    hp[idx_new < 0] = 0.0
    to = lambda a: jnp.asarray(a)
    Jgg = CK.gather_j_tiles(to(Jhat), to(idx_new), to(idx_prev))
    return (Jgg, to(vals).astype(dtype), to(mbar), to(hp),
            to(count_new), to(count_prev))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interpret_kernel_bitwise_f32(seed):
    args = _ragged_inputs(seed)
    out_k = CF.fused_update_pallas(*args, interpret=True)
    out_r = CF.fused_reference(*args)
    assert out_k.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_interpret_kernel_bf16_bounded():
    args = _ragged_inputs(3, dtype=jnp.bfloat16)
    out_k = CF.fused_update_pallas(*args, interpret=True)
    out_r = CF.fused_reference(*args)
    assert out_k.dtype == jnp.bfloat16
    a = np.asarray(out_k, np.float32)
    b = np.asarray(out_r, np.float32)
    # same f32 accumulation; only the single bf16 output cast may differ
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=1e-2)


def test_kernel_dead_rows_exact_zero():
    args = _ragged_inputs(4)
    out = np.asarray(CF.fused_update_pallas(*args, interpret=True))
    count_new = np.asarray(args[4])
    for b in range(out.shape[0]):
        assert (out[b, count_new[b]:] == 0.0).all()


def test_kernel_multi_lane_grid():
    """Pc_pad spanning several 128-lane grid blocks."""
    args = _ragged_inputs(5, Pc_pad=384)
    out_k = CF.fused_update_pallas(*args, interpret=True)
    out_r = CF.fused_reference(*args)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


# ---------------------------------------------------------------------------
# 2. engine level: fused backend vs compact backend and the dense oracle
# ---------------------------------------------------------------------------

def _setup(kind, sparsity, seed=0, n=24, T=6, B=4, n_in=5, ragged=True):
    cfg = EGRUConfig(n_hidden=n, n_in=n_in, n_out=3, kind=kind)
    params = cells.init_params(cfg, jax.random.key(seed))
    masks = None
    if sparsity is not None:
        masks = SP.make_masks(cfg, jax.random.key(seed + 7), sparsity)
        params = SP.apply_masks(params, masks)
    xs = jax.random.normal(jax.random.key(seed + 1), (T, B, n_in))
    if ragged:   # heterogeneous per-example activity -> ragged K_b
        xs = xs * jnp.linspace(0.1, 2.0, B)[None, :, None]
    labels = jnp.array([i % 3 for i in range(B)])
    return cfg, params, masks, xs, labels


def _maxdiff(g1, g2, masks=None):
    if masks is not None:
        g1 = SP.apply_masks(g1, masks)
        g2 = SP.apply_masks(g2, masks)
    return max(float(jnp.abs(a - b).max())
               for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))


@pytest.mark.parametrize("kind", ["rnn", "gru"])
@pytest.mark.parametrize("sparsity", [0.5, 0.9])     # two distinct ColLayouts
def test_fused_matches_compact_and_dense(kind, sparsity):
    cfg, params, masks, xs, labels = _setup(kind, sparsity)
    l_d, g_d, _ = SP.sparse_rtrl_loss_and_grads(cfg, params, xs, labels,
                                                masks, backend="dense")
    l_c, g_c, _ = SP.sparse_rtrl_loss_and_grads(cfg, params, xs, labels,
                                                masks, backend="compact")
    l_f, g_f, st = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact_fused")
    assert abs(float(l_f - l_d)) < 1e-5
    assert abs(float(l_f - l_c)) < 1e-5
    assert _maxdiff(g_d, g_f, masks) < 1e-4
    assert _maxdiff(g_c, g_f, masks) < 1e-5
    assert int(jnp.max(st["overflow"])) == 0


@pytest.mark.parametrize("kind", ["rnn", "gru"])
def test_fused_pallas_interpret_path(kind):
    """interpret=True drives the tile-fed @pl.when grid through the
    engine; must agree with the XLA lowering of the same step."""
    cfg, params, masks, xs, labels = _setup(kind, 0.6, seed=2)
    l_x, g_x, _ = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact_fused")
    l_p, g_p, st = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact_fused",
        interpret=True)
    assert abs(float(l_p - l_x)) < 1e-5
    assert _maxdiff(g_x, g_p, masks) < 1e-5
    assert int(jnp.max(st["overflow"])) == 0


def test_fused_no_masks_vs_dense():
    """masks=None -> ColLayout over ALL columns; still exact."""
    cfg, params, _, xs, labels = _setup("gru", None, seed=4)
    l_d, g_d, _ = SP.sparse_rtrl_loss_and_grads(cfg, params, xs, labels,
                                                None, backend="dense")
    l_f, g_f, _ = SP.sparse_rtrl_loss_and_grads(cfg, params, xs, labels,
                                                None, backend="compact_fused")
    assert abs(float(l_f - l_d)) < 1e-5
    assert _maxdiff(g_d, g_f) < 1e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_influence_dtype(dtype):
    cfg, params, masks, xs, labels = _setup("gru", 0.7, seed=5)
    l_f, g_f, _ = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact_fused",
        influence_dtype=dtype)
    l_c, g_c, _ = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact",
        influence_dtype=dtype)
    # fused vs unfused at the SAME carry dtype: tight (identical rounding
    # points up to f32 reassociation)
    assert _maxdiff(g_f, g_c, masks) < (1e-5 if dtype == "float32" else 1e-3)
    if dtype == "bfloat16":   # bounded vs the f32 run
        _, g32, _ = SP.sparse_rtrl_loss_and_grads(
            cfg, params, xs, labels, masks, backend="compact_fused")
        scale = max(float(jnp.abs(a).max()) for a in jax.tree.leaves(g32))
        assert 0 < _maxdiff(g32, g_f, masks) < 0.05 * max(scale, 1.0)


def test_learner_carry_dtype_bf16():
    cfg, params, masks, xs, labels = _setup("gru", 0.7, seed=6)
    lr = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                  backend="compact_fused",
                                  influence_dtype="bfloat16"))
    carry = lr.init(params, masks, (xs[0], labels), t_total=xs.shape[0])
    assert carry["vals"].dtype == jnp.bfloat16
    f32 = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                   backend="compact_fused"))
    c32 = f32.init(params, masks, (xs[0], labels), t_total=xs.shape[0])
    assert c32["vals"].dtype == jnp.float32
    assert carry["vals"].nbytes * 2 == c32["vals"].nbytes


def test_fused_overflow_reported():
    """Undersized static capacity must be REPORTED, not silently wrong."""
    cfg, params, masks, xs, labels = _setup("gru", 0.5, seed=7)
    _, _, st = SP.sparse_rtrl_loss_and_grads(
        cfg, params, xs, labels, masks, backend="compact_fused",
        capacity=0.34)
    assert int(jnp.max(st["overflow"])) > 0


def test_stacked_fused_matches_compact():
    cfg = EGRUConfig(n_hidden=16, n_in=5, n_out=3, kind="gru")
    scfg = cells.stacked_config(cfg, 2)
    params = cells.init_stacked_params(scfg, jax.random.key(0))
    masks = ST.make_stacked_masks(scfg, jax.random.key(1), 0.6, block=4)
    params = ST.apply_stacked_masks(params, masks)
    xs = jax.random.normal(jax.random.key(2), (5, 3, cfg.n_in))
    xs = xs * jnp.linspace(0.2, 2.0, 3)[None, :, None]
    labels = jnp.zeros((3,), jnp.int32)
    l_c, g_c, _ = ST.stacked_rtrl_loss_and_grads(scfg, params, xs, labels,
                                                 masks, backend="compact")
    l_f, g_f, st = ST.stacked_rtrl_loss_and_grads(
        scfg, params, xs, labels, masks, backend="compact_fused")
    assert abs(float(l_f - l_c)) < 1e-5
    assert _maxdiff(g_c, g_f) < 1e-5
    assert int(np.max(np.asarray(st["overflow"]))) == 0


@pytest.mark.parametrize("layers", [1, 2])
def test_scaled_fused_matches_compact(layers):
    cfg = SC.ScaledRTRLConfig(n=16, n_in=5, n_out=3, batch=3,
                              n_layers=layers, beta_capacity=1.0,
                              sparsity=0.7)
    params, masks = SC.init_params(cfg, jax.random.key(3))
    xs = jax.random.normal(jax.random.key(4), (5, cfg.batch, cfg.n_in))
    labels = jnp.zeros((cfg.batch,), jnp.int32)
    l_c, g_c, _ = SC.rtrl_grads(cfg, params, xs, labels, masks)
    l_f, g_f, _ = SC.rtrl_grads(cfg, params, xs, labels, masks,
                                backend="compact_fused")
    assert abs(float(l_f - l_c)) < 1e-5
    assert _maxdiff(g_c, g_f) < 1e-5


# ---------------------------------------------------------------------------
# 3. contract level: segment table, ladder, rejections
# ---------------------------------------------------------------------------

def test_segment_table_covers_live_columns():
    cfg = EGRUConfig(n_hidden=16, n_in=5, n_out=3, kind="gru")
    layout = SP.flat_layout(cfg)
    masks = SP.make_masks(cfg, jax.random.key(9), 0.6)
    cl = SP.col_layout(layout, masks)
    segs = CF.fused_segments(layout, cl)
    live = int(np.sum(np.asarray(cl.live) > 0))
    covered = sum(e - s for s, e, *_ in segs)
    assert covered == live                      # every live column, exactly
    pos = 0
    for s, e, kind, *_ in segs:                 # ordered, non-overlapping
        assert s >= pos and e > s
        assert kind in ("diag", "r", "theta")
        pos = e
    kinds = [k for _, _, k, *_ in segs]
    assert kinds.count("r") == 1 and kinds.count("theta") == 1


def test_segment_table_rejects_tracer():
    cfg = EGRUConfig(n_hidden=8, n_in=3, n_out=2, kind="rnn")
    layout = SP.flat_layout(cfg)
    cl = SP.col_layout(layout, None)

    def f(gate):
        return CF.fused_segments(layout, dataclasses.replace(cl, gate=gate))[0][0]

    with pytest.raises(ValueError, match="concrete ColLayout"):
        jax.jit(f)(jnp.asarray(cl.gate))


def test_capacity_ladder():
    for K in (8, 16, 64, 136, 152):
        ladder = CF.capacity_ladder(K)
        assert ladder[-1] == K
        assert list(ladder) == sorted(set(ladder))
        assert all(r % 8 == 0 or r == K for r in ladder)
        assert all(0 < r <= K for r in ladder)


def test_fused_rejects_rewirable():
    cfg = EGRUConfig(n_hidden=16, n_in=5, n_out=3, kind="gru")
    with pytest.raises(ValueError, match="rewirable"):
        make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                 backend="compact_fused", rewirable=True))


def test_bf16_rejected_off_compact_carries():
    cfg = EGRUConfig(n_hidden=16, n_in=5, n_out=3, kind="gru")
    for backend in ("dense", "pallas"):
        with pytest.raises(ValueError, match="compact"):
            make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                     backend=backend,
                                     influence_dtype="bfloat16"))
    with pytest.raises(ValueError):
        SP.influence_carry_dtype("float16")


def test_fused_rejects_col_compact_false():
    cfg = EGRUConfig(n_hidden=16, n_in=5, n_out=3, kind="gru")
    params = cells.init_params(cfg, jax.random.key(0))
    masks = SP.make_masks(cfg, jax.random.key(1), 0.5)
    lr = make_learner(LearnerSpec(engine="sparse", cfg=cfg,
                                  backend="compact_fused", col_compact=False))
    xs = jnp.zeros((2, cfg.n_in))
    with pytest.raises(ValueError, match="col"):
        lr.init(params, masks, (xs, jnp.zeros((2,), jnp.int32)), t_total=4)
