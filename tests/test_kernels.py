"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes, dtypes and sparsity levels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import compact, ops, ref


@pytest.mark.parametrize("B,n,P", [(1, 8, 128), (4, 32, 256), (2, 64, 384),
                                   (3, 24, 130)])
@pytest.mark.parametrize("beta", [0.0, 0.5, 0.9])
def test_influence_kernel_matches_ref(B, n, P, beta):
    key = jax.random.key(int(B * n + P + beta * 100))
    ks = jax.random.split(key, 6)
    hp = jax.random.uniform(ks[0], (B, n))
    hp = jnp.where(jax.random.uniform(ks[1], (B, n)) < beta, 0.0, hp)
    Jhat = jax.random.normal(ks[2], (B, n, n))
    M = jax.random.normal(ks[3], (B, n, P))
    M = jnp.where(jax.random.uniform(ks[4], (B, n, 1)) < 0.3, 0.0, M)
    Mbar = jax.random.normal(ks[5], (B, n, P))
    out_k = ops.influence_update(hp, Jhat, M, Mbar)
    out_r = ref.influence_ref(hp, Jhat, M, Mbar)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("omega", [0.0, 0.6, 0.9])
def test_influence_kernel_with_param_masks(omega):
    key = jax.random.key(3)
    B, n, P = 2, 32, 256
    ks = jax.random.split(key, 6)
    jmask = (jax.random.uniform(ks[0], (n, n)) > omega).astype(jnp.float32)
    col_mask = (jax.random.uniform(ks[1], (P,)) > omega).astype(jnp.float32)
    hp = jax.random.uniform(ks[2], (B, n))
    Jhat = jax.random.normal(ks[3], (B, n, n)) * jmask.T[None]
    M = jax.random.normal(ks[4], (B, n, P)) * col_mask[None, None]
    Mbar = jax.random.normal(ks[5], (B, n, P)) * col_mask[None, None]
    out_k = ops.influence_update(hp, Jhat, M, Mbar, jmask=jmask,
                                 col_mask=col_mask)
    out_r = ref.influence_ref(hp, Jhat, M, Mbar)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,n,m", [(2, 16, 128), (4, 64, 256), (1, 40, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_event_matmul_matches_ref(B, n, m, dtype):
    key = jax.random.key(B + n + m)
    a = (jax.random.uniform(key, (B, n)) > 0.7).astype(dtype)
    R = jax.random.normal(jax.random.fold_in(key, 1), (n, m)).astype(dtype)
    y_k = ops.event_matmul(a, R)
    y_r = ref.event_matmul_ref(a, R)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("steps", [1, 3])
def test_compact_influence_exact_when_capacity_sufficient(steps):
    key = jax.random.key(0)
    B, n, P, K = 3, 24, 64, 20
    J = jax.random.normal(jax.random.fold_in(key, 99), (B, n, n))
    M_dense = jnp.zeros((B, n, P))
    Mc = compact.compact_init(B, K, P)
    for t in range(steps):
        ks = jax.random.split(jax.random.fold_in(key, t), 3)
        hp = jnp.where(jax.random.uniform(ks[0], (B, n)) < 0.5, 0.0,
                       jax.random.uniform(ks[1], (B, n)))
        Mbar = jax.random.normal(ks[2], (B, n, P)) * (hp != 0)[..., None]
        M_dense = ref.influence_ref(hp, J, M_dense, Mbar)
        Mc, overflow = compact.compact_influence_step(hp, J, Mc, Mbar, K=K)
        assert int(overflow.max()) == 0
    np.testing.assert_allclose(
        np.asarray(compact.compact_to_dense(Mc, n)), np.asarray(M_dense),
        atol=1e-5, rtol=1e-5)


def test_compact_overflow_reported():
    B, n, P, K = 1, 16, 8, 4
    hp = jnp.ones((B, n))           # all rows active >> capacity
    J = jnp.zeros((B, n, n))
    Mc = compact.compact_init(B, K, P)
    Mc, overflow = compact.compact_influence_step(
        hp, J, Mc, jnp.ones((B, n, P)), K=K)
    assert int(overflow[0]) == n - K


# --- tile lookup: compare-and-select vs the per-element gather --------------

def _gather_tiles_ref(A, idx_row, idx_col):
    """The lookup as an index gather: rows by a row gather, columns by a
    `take_along_axis` with one index per tile element; dead slots read 0."""
    B, K = idx_row.shape
    n_row, n_col = A.shape[-2:]
    Ag = A[jnp.arange(B)[:, None], jnp.clip(idx_row, 0, n_row - 1)]
    Agg = jnp.take_along_axis(
        Ag, jnp.broadcast_to(jnp.clip(idx_col, 0, n_col - 1)[:, None, :],
                             (B, K, idx_col.shape[1])), axis=2)
    live = (idx_row >= 0)[:, :, None] & (idx_col >= 0)[:, None, :]
    return jnp.where(live, Agg, 0.0)


def _lookup_case(kind, poison, col_cap, seed):
    """(A or None, AT or None, idx_row, idx_col, the dense A the indices
    read).  Row capacity 16 > n_row = 12 pads with dead slots, as a column
    capacity above n_col does; unit 0 and the last unit are inactive
    everywhere, so a clipped dead slot would read them; `poison` puts inf
    or NaN there."""
    n_row, n_col = {"square": (12, 12), "rect": (12, 20), "AT": (12, 20)}[kind]
    key = jax.random.key(seed)
    ks = jax.random.split(key, 3)

    def idx(k, n, K):
        on = jax.random.bernoulli(k, 0.6, (3, n)).at[:, 0].set(False)
        return compact.compact_rows(on.at[:, n - 1].set(False), K)[0]

    idx_row, idx_col = idx(ks[0], n_row, 16), idx(ks[1], n_col, col_cap)
    A = jax.random.normal(ks[2], (3, n_row, n_col))
    if poison is not None:
        bad = jnp.inf if poison == "inf" else jnp.nan
        A = A.at[:, 0].set(bad).at[:, :, 0].set(bad)
        A = A.at[:, n_row - 1].set(-bad).at[:, :, n_col - 1].set(bad)
    if kind == "AT":
        AT = A[0].T
        return None, AT, idx_row, idx_col, jnp.broadcast_to(AT.T, A.shape)
    return A, None, idx_row, idx_col, A


@pytest.mark.parametrize("vmapped", [False, True])
@pytest.mark.parametrize("poison", [None, "inf", "nan"])
@pytest.mark.parametrize("rows", ["select", "gather"])
@pytest.mark.parametrize("col_cap", [8, 24])
@pytest.mark.parametrize("kind", ["square", "rect", "AT"])
def test_gather_tiles_bitwise_equals_index_gather(monkeypatch, kind, col_cap,
                                                  rows, poison, vmapped):
    """The lookup (12 rows, selected or gathered by the row width rule;
    12 or 20 columns by compare-and-select, 8 or 24 column slots) equals
    the per-element gather bit for bit (zeros of either sign alike), dead
    slots and K > n padding read 0, an inf or NaN nobody selects never
    leaks into a tile, and a gather is compiled only for gathered rows."""
    monkeypatch.setattr(compact, "ROW_SELECT_MAX_WIDTH",
                        12 if rows == "select" else 11)
    cases = [_lookup_case(kind, poison, col_cap, s)
             for s in range(3 if vmapped else 1)]
    if vmapped:
        stack = lambda i: None if cases[0][i] is None else jnp.stack(
            [c[i] for c in cases])
        args, dense = tuple(stack(i) for i in range(4)), stack(4)
    else:
        args, dense = cases[0][:4], cases[0][4]

    def look(A, AT, ir, ic):
        return compact.gather_tiles(A, ir, ic, AT=AT)

    ref = _gather_tiles_ref
    if vmapped:
        look = jax.vmap(look, in_axes=tuple(None if a is None else 0
                                            for a in args))
        ref = jax.vmap(ref)
    got, want = look(*args), ref(dense, *args[2:])
    assert got.shape == want.shape == args[2].shape + args[3].shape[-1:]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()
    lowered = jax.jit(look).lower(*args)
    assert (" gather(" in lowered.compiler_ir("hlo").as_hlo_text()) \
        == (rows == "gather")
    assert "tile_select" in lowered.as_text(debug_info=True)


@pytest.mark.parametrize("width", [16, 15])
@pytest.mark.parametrize("trailing", [(), (3,)])
def test_take_rows_dead_slots_read_zero(monkeypatch, trailing, width):
    """Rows of a [B, n] or [B, n, 3] table (n = 16), selected (width 16)
    or gathered (15), equal the index gather's bits; a dead slot reads 0,
    not row 0's inf; only gathered rows compile to a gather."""
    monkeypatch.setattr(compact, "ROW_SELECT_MAX_WIDTH", width)
    x = jax.random.uniform(jax.random.key(3), (4, 16) + trailing)
    x = x.at[:, 0].set(jnp.inf)
    on = jax.random.bernoulli(jax.random.key(4), 0.5, (4, 16)).at[:, 0].set(
        False)
    idx, _ = compact.compact_rows(on, 16)
    got = compact.take_rows(x, idx)
    live = (idx >= 0).reshape(idx.shape + (1,) * len(trailing))
    want = jnp.where(live, x[jnp.arange(4)[:, None], jnp.clip(idx, 0, 15)],
                     0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()
    hlo = jax.jit(lambda x, i: compact.take_rows(x, i)).lower(
        x, idx).compiler_ir("hlo").as_hlo_text()
    assert (" gather(" in hlo) == (width < 16)


# --- chunked flash attention vs naive oracle --------------------------------

@pytest.mark.parametrize("S,H,KV,causal,window",
                         [(64, 4, 4, True, 0), (64, 4, 2, True, 0),
                          (64, 4, 2, False, 0), (128, 4, 2, True, 32),
                          (96, 6, 2, True, 0)])
def test_chunked_flash_matches_naive(S, H, KV, causal, window):
    from repro.configs import get_config, smoke_config
    from repro.models.attention import flash_attention
    cfg = smoke_config(get_config("yi-6b")).replace(
        n_heads=H, n_kv_heads=KV, head_dim=16, attn_q_chunk=16,
        attn_kv_chunk=32, local_window=window)
    key = jax.random.key(0)
    B = 2
    q = jax.random.normal(key, (B, S, H, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, 16))
    out = flash_attention(cfg, q, k, v, causal=causal, window=window)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-4, rtol=2e-4)


def test_chunked_flash_grads_flow():
    """lax.cond block skipping must stay differentiable."""
    from repro.configs import get_config, smoke_config
    from repro.models.attention import flash_attention
    cfg = smoke_config(get_config("yi-6b")).replace(
        n_heads=2, n_kv_heads=2, head_dim=8, attn_q_chunk=8, attn_kv_chunk=8)
    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 32, 2, 8))

    def f(q):
        return flash_attention(cfg, q, q, q, causal=True).sum()

    g = jax.grad(f)(q)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.abs(g).sum()) > 0


# --- WKV chunked form vs sequential recurrence ------------------------------

@pytest.mark.parametrize("L,D", [(8, 8), (16, 16)])
def test_wkv_chunk_matches_sequential(L, D):
    from repro.models.rwkv import wkv_chunk
    key = jax.random.key(1)
    B, H = 2, 3
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, H, L, D))
    k = jax.random.normal(ks[1], (B, H, L, D))
    v = jax.random.normal(ks[2], (B, H, L, D))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, L, D)))
    u = jax.random.normal(ks[4], (H, D))
    S0 = jax.random.normal(jax.random.fold_in(key, 9), (B, H, D, D))
    o_c, S_c = wkv_chunk(r, k, v, logw, u, S0)
    o_r, S_r = ref.wkv_chunk_ref(r, k, v, logw, u, S0)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(S_c), np.asarray(S_r),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("T,D,L", [(32, 8, 8), (64, 16, 16)])
def test_wkv_pallas_kernel_matches_sequential(T, D, L):
    """State-in-VMEM Pallas WKV (interpret mode) vs the exact recurrence."""
    from repro.kernels.wkv import wkv_pallas
    key = jax.random.key(0)
    B, H = 2, 3
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    v = jax.random.normal(ks[2], (B, H, T, D))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, D)))
    u = jax.random.normal(ks[4], (H, D))
    o_k = wkv_pallas(r, k, v, logw, u, chunk=L)
    S = jnp.zeros((B, H, D, D))
    outs = []
    for c in range(T // L):
        sl = slice(c * L, (c + 1) * L)
        o, S = ref.wkv_chunk_ref(r[:, :, sl], k[:, :, sl], v[:, :, sl],
                                 logw[:, :, sl], u, S)
        outs.append(o)
    o_r = jnp.concatenate(outs, axis=2)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=5e-4, rtol=5e-4)
