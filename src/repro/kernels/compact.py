"""Capacity-based row compaction: the TPU-native realisation of the paper's
beta~^2 savings for *unstructured* activity sparsity.

Block-granular skipping (influence.py) only pays off when zeros cluster into
whole (8,128) tiles; random unit-level sparsity at beta=0.5 leaves ~1-0.5^8
of 8-row blocks active.  Compaction instead gathers the <=K active rows into
a dense buffer (K a static capacity, like MoE token capacity), runs a dense
[K x K_prev] x [K_prev x P] MXU matmul, and scatters back:

    FLOPs = K * K_prev * P  ~=  beta~(t) beta~(t-1) n^2 p      (exact!)

The influence matrix is carried in compact form (values [B,K,P] + active-row
indices [B,K]) across timesteps, so memory is the paper's beta~ n p too.
Rows beyond capacity are dropped (capacity_factor sized so overflow ~never
happens; overflow count is reported so callers can assert exactness).

DUAL (row x column) compaction: every function here is width-agnostic in P,
so the same contraction/gather/extraction machinery runs unchanged when the
caller carries the parameter axis column-compact at Pc ~= w~ P
(`repro.core.sparse_rtrl.ColLayout` — the fixed Sec.-6 masks make the live
column set static).  vals become [B, K, Pc_pad]; `compact_update` then does
K * K_prev * Pc MXU work — the paper's COMBINED  w~ beta~(t) beta~(t-1) n^2 p
— and `compact_grads` emits the compact flat gradient [Pc_pad] that
`sparse_rtrl.cols_to_flat` scatters back once per sequence.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DEAD = -1   # THE dead-slot sentinel: every idx array here is -1 or in [0, n)


class CompactInfluence(NamedTuple):
    vals: jax.Array       # [B, K, P]   compacted rows of M
    idx: jax.Array        # [B, K]      row index per slot (-1 = dead slot)
    count: jax.Array      # [B]         number of live rows


def check_idx(idx: jax.Array, n: int) -> None:
    """Assert the -1 dead-slot convention on CONCRETE index arrays: every
    entry is DEAD or a valid row in [0, n).  A no-op under jit tracing —
    Tracers carry no values — so the check costs nothing on the hot path
    but catches convention drift in eager tests and interpret-mode runs."""
    if isinstance(idx, jax.core.Tracer):
        return
    a = np.asarray(idx)
    bad = (a != DEAD) & ((a < 0) | (a >= n))
    if bad.any():
        raise ValueError(
            f"compact idx violates the -1 sentinel convention: entries "
            f"{np.unique(a[bad])} outside {{-1}} u [0, {n})")


def compact_rows(dense_rows_mask: jax.Array, K: int) -> tuple[jax.Array, jax.Array]:
    """dense_rows_mask: [B, n] bool -> (idx [B,K], -1 = dead slot; count [B])."""
    B, n = dense_rows_mask.shape
    # stable order: active rows first, by index
    key = jnp.where(dense_rows_mask, 0, 1) * (n + 1) + jnp.arange(n)[None]
    order = jnp.argsort(key, axis=1)[:, :K]                     # [B, K]
    if K > n:   # alignment can push capacity past n: pad with dead slots
        order = jnp.pad(order, ((0, 0), (0, K - n)), constant_values=DEAD)
    count = dense_rows_mask.sum(axis=1)
    slot_live = jnp.arange(K)[None, :] < count[:, None]
    idx = jnp.where(slot_live, order, DEAD)
    return idx, count


def compact_init(B: int, K: int, P: int,
                 dtype: jnp.dtype = jnp.float32) -> CompactInfluence:
    return CompactInfluence(jnp.zeros((B, K, P), dtype),
                            jnp.full((B, K), DEAD, jnp.int32),
                            jnp.zeros((B,), jnp.int32))


# `take_rows` selects rows up to this width and gathers whole rows above
# it.  A lone J-hat lookup on one TPU v5e (K = n, batch 32; PERF.md,
# section 6) ran faster with its rows selected at n = 16 (17x when vmapped
# over 1024 slots) and with them gathered at n = 64 to 256 (1.5-2x); 32
# lies between.  Wide rows must not be selected: compiled for a v5e at
# n = 512, XLA fuses the row select into the column select and stores a
# 4-D select tensor of B n^3 floats, 16 GiB.
ROW_SELECT_MAX_WIDTH = 32


def take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x[b, idx[b, k]] for x [Bx, n, ...] (Bx = B, or 1 for a table shared
    by the batch) and idx [B, K]; a dead slot (-1) reads 0.

    Up to `ROW_SELECT_MAX_WIDTH` rows this is a compare-and-select
    reduction, sum_i where(i == idx[b, k], x[b, i], 0), which the vector
    unit runs as one fused elementwise-and-reduce pass; an index gather
    would run an element (or a short row) at a time.  It is exact: every
    term but the selected one is an exact zero, so the sum is that entry in
    any order (only a zero's sign can differ), and an inf or NaN in a row
    nobody selects never enters a product.  Wider tables are gathered a
    row at a time."""
    n = x.shape[1]
    if n <= ROW_SELECT_MAX_WIDTH:
        hit = idx[:, :, None] == jnp.arange(n)                  # [B, K, n]
        hit = hit.reshape(hit.shape + (1,) * (x.ndim - 2))
        return jnp.where(hit, x[:, None], 0).sum(axis=2)
    rows = x[jnp.arange(x.shape[0])[:, None], jnp.clip(idx, 0, n - 1)]
    live = (idx >= 0).reshape(idx.shape + (1,) * (x.ndim - 2))
    return jnp.where(live, rows, 0)                             # [B, K, ...]


def gather_tiles(A: jax.Array | None, idx_row: jax.Array,
                 idx_col: jax.Array, *, AT: jax.Array | None = None):
    """[B, K, K_col] tiles of a (possibly rectangular) Jacobian: rows at
    `idx_row`, columns at `idx_col`; a dead slot (sentinel -1, asserted by
    `check_idx`) in either reads 0.  Pass the dense per-example ``A``
    [B, n_row, n_col] (data-dependent Jacobians, e.g. EGRU J-hat or the
    cross-layer B-hat), or ``AT`` [n_col, n_row] — a weight matrix whose
    TRANSPOSE is the Jacobian (R for the vanilla RNN's J-hat, W for its
    B-hat) — so tiles are looked up directly and [B, n_row, n_col] is
    never materialized.

    Scope `tile_select`: the rows by `take_rows` (selected or gathered by
    their width), then the columns by the exact compare-and-select
    Agg[b, k, l] = sum_j where(j == idx_col[b, l], Ag[b, k, j], 0), which
    XLA fuses into its reduction, so no [.., K, K_col, n_col] tensor is
    stored.  On a TPU v5e the per-element column gather this replaces was
    slower at every width timed (PERF.md, section 6)."""
    if AT is not None:
        n_col, n_row = AT.shape
    else:
        n_row, n_col = A.shape[-2], A.shape[-1]
    check_idx(idx_row, n_row)
    check_idx(idx_col, n_col)
    with jax.named_scope("tile_select"):
        Ag = take_rows(A if AT is None else AT.T[None], idx_row)  # [B,K,n_col]
        hit = idx_col[:, None, :, None] == jnp.arange(n_col)
        return jnp.where(hit, Ag[:, :, None, :], 0).sum(axis=3)


def gather_j_tiles(Jhat: jax.Array | None, idx_new: jax.Array,
                   idx_prev: jax.Array, *, R: jax.Array | None = None):
    """[B, K, K_prev] tiles of the (square) step Jacobian J-hat: rows at
    the newly-active unit indices, columns at the previously-active ones,
    by compare-and-select.  Thin wrapper over `gather_tiles`."""
    return gather_tiles(Jhat, idx_new, idx_prev, AT=R)


def compact_update(Jgg: jax.Array, vals_prev: jax.Array, mbar_rows: jax.Array,
                   hp_rows: jax.Array, idx_new: jax.Array, count: jax.Array,
                   K: int) -> tuple[CompactInfluence, jax.Array]:
    """The shared compact contraction:  vals = hp ⊙ (Jgg @ vals_prev + M-bar).

    Jgg [B,K,Kprev] (dead prev columns already zeroed); mbar_rows [B,K,P]
    gathered at the new active rows; hp_rows [B,K] with dead slots zeroed;
    idx_new [B,K] with sentinel -1 for dead slots.  K*K_prev*P MXU work.
    The contraction accumulates in f32 regardless of the carry dtype
    (bf16 carries get f32 MXU accumulation, cast back on write)."""
    T = jnp.einsum("bkl,blp->bkp", Jgg, vals_prev,
                   preferred_element_type=jnp.float32)
    vals = (hp_rows[:, :, None]
            * (T + mbar_rows.astype(jnp.float32))).astype(vals_prev.dtype)
    overflow = jnp.maximum(count - K, 0)
    return CompactInfluence(vals, idx_new, jnp.minimum(count, K)), overflow


@functools.partial(jax.jit, static_argnames=("K",))
def compact_influence_step(hp: jax.Array, Jhat: jax.Array,
                           Mc: CompactInfluence, Mbar: jax.Array, K: int):
    """One RTRL influence update in compact form.

    hp [B,n]; Jhat [B,n,n]; Mbar [B,n,P]; returns (Mc', overflow [B]).
    FLOPs scale as K * K * P instead of n * n * P."""
    B, n, P = Mbar.shape
    idx_new, count_new = compact_rows(hp != 0.0, K)             # rows of M_t
    bidx = jnp.arange(B)[:, None]
    safe_new = jnp.clip(idx_new, 0, n - 1)
    Jgg = gather_j_tiles(Jhat, idx_new, Mc.idx)
    Mbar_g = Mbar[bidx, safe_new]                               # [B, K, P]
    hp_g = take_rows(hp, idx_new)                               # [B, K]
    return compact_update(Jgg, Mc.vals, Mbar_g, hp_g, idx_new, count_new, K)


def compact_grads(vals: jax.Array, idx: jax.Array, cbar: jax.Array):
    """Fused gradient extraction  dL/dw = c-bar^T M  on the compact form.

    c-bar [B, n] is gathered at the active row indices and contracted with
    vals [B, K, P] directly — the dense [B, n, P] influence tensor is never
    scattered back.  Returns the flat gradient [P] in f32 (bf16 carries are
    upcast before the contraction).

    The contraction runs per example ([B, K] x [B, K, P] -> [B, P]) with an
    explicit batch sum rather than one merged (b, k) reduction: the merged
    form lets XLA re-block the reduction when a leading axis is added, so
    its rounding changes under `jax.vmap` — and the fleet's slot-batched
    update chunk (runtime/fleet.py) must be bit-identical to the solo
    trainer's."""
    n = cbar.shape[1]
    check_idx(idx, n)
    safe = jnp.clip(idx, 0, n - 1)
    live = idx >= 0
    cb = jnp.take_along_axis(cbar, safe, axis=1) * live         # [B, K]
    return jnp.einsum("bk,bkp->bp", cb, vals,
                      preferred_element_type=jnp.float32).sum(axis=0)


def compact_to_dense(Mc: CompactInfluence, n: int) -> jax.Array:
    """Scatter back to [B, n, P] (for verification / credit assignment).
    Dead slots (idx == -1, asserted) land in a scratch row that is cropped."""
    check_idx(Mc.idx, n)
    B, K, P = Mc.vals.shape
    out = jnp.zeros((B, n + 1, P), Mc.vals.dtype)
    idx = jnp.where(Mc.idx < 0, n, Mc.idx)
    out = out.at[jnp.arange(B)[:, None], idx].set(Mc.vals)
    return out[:, :n]
