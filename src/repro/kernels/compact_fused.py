"""Fused dual-compact influence update: contract + Mbar + scale, one
invocation per step, ragged per example.

This is the accelerator-native form of the paper's combined
omega~ beta~(t) beta~(t-1) n^2 p  influence-update cost (Table 1, "RTRL +
both"): the row-compact path (compact.py) realises the FLOP count but as an
unfused gather -> [K x K'] x [K' x Pc] einsum -> scale chain, and K is the
BATCH-WIDE max active-row count, so batch members with fewer active rows pay
for the busiest one.  Here the whole update

    M_t[rows] = D(hp) [ J-hat[rows, prev rows] M_{t-1} + M-bar[rows] ]

runs as ONE kernel whose grid blocks map directly onto the paper's cost
factors:

  grid axis 1 (row blocks of size bk)      beta~(t) n      active NEW rows
  in-kernel l-loop (prev-row blocks, bl)   beta~(t-1) n    active PREV rows
  grid axis 2 (column blocks of size bp)   omega~ p        live param columns

Capacity is RAGGED PER EXAMPLE: the live row counts are scalar-prefetched,
and grid blocks past example b's live count are skipped with @pl.when (row
blocks) / lax.cond (prev-row blocks), so executed compute is
Sigma_b K_b K'_b Pc instead of B K_max^2 Pc — the batch tax dies without
changing the carry pytree shape ([B, K, Pc] + [B, K] indices, as before).

Two lowerings of the SAME block structure:

  * `fused_update_pallas` — the TPU kernel (pl.pallas_call): the [B, K, K']
    J tiles are looked up in XLA (`compact.gather_j_tiles`, 1/Pc of the
    carry's bytes; at the paper's widths an exact compare-and-select
    reduction, fused, with no gather) and enter as a blocked input, so the
    kernel body holds only static slices, which Mosaic lowers; the
    (bk x bl) x (bl x bp) partial products accumulate in f32 on the MXU,
    M-bar adds and the hp diagonal scale apply before the single output
    write.  Checked on CPU with interpret=True
    (tests/test_compact_fused.py) and compiled for a described v5e
    (tests/test_tpu_compile.py).
  * `fused_update_blocks` — the XLA lowering for hosts without a TPU grid:
    the same per-example blocking, with the data-dependent skip realised as
    a lax.switch over a static capacity ladder (smallest 8-aligned rung
    covering every example's live count) — real branches, so the dead-row
    margin is never multiplied — and the M-bar segments generated INLINE at
    each gate's compact column range (`fused_segments`), never materialising
    the [B, K, Pc] immediate-influence buffer the unfused path builds.

Both lowerings accumulate in f32 regardless of the carry dtype: with the
opt-in bf16 influence carry (influence_dtype=, threaded from
`FlatLayout`/`ColLayout` through the learners), values are read bf16,
multiplied-accumulated f32, and cast back on the single write — halving
carry bytes and bandwidth at bounded round-off.

Cross-references: kernels/influence.py is the block-mask (non-compact)
sibling of this kernel; kernels/compact.py holds the carry representation
and the unfused reference the parity tests pin against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# gate-segment kinds on the compact column axis (see fused_segments)
_DIAG, _RGATE, _THETA = "diag", "r", "theta"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _ceil8(v: int) -> int:
    return -(-int(v) // 8) * 8


def capacity_ladder(K: int) -> tuple[int, ...]:
    """Static capacity rungs for the XLA lowering's ragged switch: 8-aligned
    fractions of K.  The executed branch is the smallest rung covering every
    example's live row count — the static-shape realisation of the kernel's
    per-example @pl.when skip."""
    return tuple(sorted({_ceil8(K // 2), _ceil8(5 * K // 8),
                         _ceil8(3 * K // 4), _ceil8(7 * K // 8), int(K)}))


# ---------------------------------------------------------------------------
# Static gate segments of the compact column axis
# ---------------------------------------------------------------------------

def fused_segments(layout, cl, layer: int = 0):
    """Static per-gate segment table of a ColLayout's compact column axis.

    Returns a tuple of (start, end, kind, coef_key, g_key, q[], j[]) with the
    column index arrays CONCRETE (host numpy) — the fused XLA lowering
    generates each gate's M-bar block directly at its own column range, so
    the table must be built eagerly from a concrete ColLayout (masks are
    fixed per compile; rewiring swaps ColLayouts and therefore recompiles,
    which is why the fused backend rejects `rewirable` specs).

    kind: 'diag' (u/z, rnn v: one column group per unit, coefficient
    diagonal in (row unit, column unit)), 'r' (the GRU r gate, dense in the
    column unit through R_z), 'theta' (the -I threshold block)."""
    from repro.core import sparse_rtrl as SP
    if isinstance(cl.gate, jax.core.Tracer):
        raise ValueError("fused_segments needs a concrete ColLayout "
                         "(build it eagerly; the fused backend does not "
                         "support runtime-swapped ColLayouts)")
    gate = np.asarray(cl.gate)
    layr = np.asarray(cl.layer)
    live = np.asarray(cl.live)
    q = np.asarray(cl.q)
    j = np.asarray(cl.j)
    segs = []
    if layout.kind == "rnn":
        table = [(0, _DIAG, "v_diag_coef", "v_g")]
    else:
        gid = {g: i for i, g in enumerate(layout.gates)}
        table = [(gid["u"], _DIAG, "u_diag_coef", "u_g"),
                 (gid["r"], _RGATE, "r_coef", "r_g"),
                 (gid["z"], _DIAG, "z_diag_coef", "z_g"),
                 (SP.COL_GATE_THETA, _THETA, None, None)]
    for g, kind, ck, gk in table:
        sel = np.nonzero((gate == g) & (layr == layer) & (live > 0))[0]
        if sel.size == 0:
            continue
        if not np.all(np.diff(sel) == 1):
            raise ValueError(f"gate {g} columns not contiguous in ColLayout")
        segs.append((int(sel[0]), int(sel[-1]) + 1, kind, ck, gk,
                     q[sel].astype(np.int32), j[sel].astype(np.int32)))
    segs.sort()
    return tuple(segs)


def _mbar_segment(seg, mbar, safe_rows, n):
    """One gate's M-bar block [rows, seg width] for ONE example, generated
    at compact width from the cell's mbar pieces (hp-ungated)."""
    s, e, kind, ck, gk, qg, jg = seg
    qj = jnp.asarray(qg)
    jj = jnp.asarray(jg)
    if kind == _THETA:
        return -(qj[None, :] == safe_rows[:, None]).astype(jnp.float32)
    if kind == _DIAG:
        coef = mbar[ck][safe_rows]                       # [rows]
        G = mbar[gk][jj]                                 # [width]
        return (coef[:, None] * G[None, :]
                * (qj[None, :] == safe_rows[:, None]))
    # r gate: value[k, c] = r_coef[row_k, q(c)] * r_g[j(c)]
    rc = mbar[ck][safe_rows][:, qj]                      # [rows, width]
    return rc * mbar[gk][jj][None, :]


# ---------------------------------------------------------------------------
# XLA lowering: per-example blocked dots + inline M-bar, ragged via switch
# ---------------------------------------------------------------------------

def fused_update_blocks(mbar, safe_new, hp_rows, Jgg, vals, count_new,
                        count_prev, segments, *, hp_full=None, below=None,
                        n: int | None = None,
                        ladder: tuple[int, ...] | None = None) -> jax.Array:
    """vals_t = D(hp)[J-tiles vals_{t-1} + M-bar]  — fused, ragged, XLA.

    mbar: per-example-indexable cell pieces (dict of [B, ...] arrays);
    Jgg [B, K, K'] gathered J tiles (dead prev columns zeroed); vals
    [B, K', Pc_pad] compact carry (any dtype; f32 accumulation); segments
    from `fused_segments`; hp_full [B, n] the un-gathered pseudo-derivative
    (defaults to a scatter of hp_rows — pass it to skip that).  `below=
    (Bgg, vals_below)` adds the stacked cross-layer injection inside the
    same fused contraction.  Returns the new [B, K, Pc_pad] carry in
    vals.dtype, dead rows exactly zero.

    Per example: the contraction rung is chosen from the ladder PER
    EXAMPLE (the static-shape form of the kernel's @pl.when skip), the
    dot emits ALL K output rows directly — rows past the live count have
    hp_rows == 0, so they are exactly zero without a separate pad copy —
    the 'diag'/'theta' M-bar segments (one nonzero per column)
    scatter-add in place, and only the dense 'r' segment pays a
    blockwise add.  Columns outside this layer's gate segments (other
    layers of a stacked axis; the pad tail) keep the contraction alone:
    cross-layer influence flows through the `below` injection;
    single-layer pad columns stay exactly 0."""
    B, K, _ = Jgg.shape
    Pc_pad = vals.shape[-1]
    ladder = capacity_ladder(K) if ladder is None else ladder
    if n is None:
        n = int(np.max([np.max(seg[5]) for seg in segments])) + 1 \
            if segments else Jgg.shape[1]
    if hp_full is None:
        trap = jnp.where(hp_rows != 0.0, safe_new, n)     # dead slots -> n
        hp_full = jnp.zeros((B, n + 1)).at[
            jnp.arange(B)[:, None], trap].set(hp_rows)[:, :n]
    Jhp = hp_rows[:, :, None] * Jgg          # fold the diagonal scale in

    def body(Ct, b):
        def branch():
            ob = lax.dot_general(
                Jhp[b][:, :Ct], vals[b, :Ct].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if below is not None:
                Bgg, vals_b = below
                ob = ob + lax.dot_general(
                    hp_rows[b, :, None] * Bgg[b],
                    vals_b[b].astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            # unit -> compact row position (n = invalid/dead sentinel slot)
            rows = jnp.where(hp_rows[b, :Ct] != 0.0, safe_new[b, :Ct], n)
            inv = jnp.full((n + 1,), -1, jnp.int32).at[rows].set(
                jnp.arange(Ct, dtype=jnp.int32))
            for seg in segments:
                s, e, kind, ck, gk, qg, jg = seg
                qj = jnp.asarray(qg)
                jj = jnp.asarray(jg)
                if kind == _RGATE:       # dense in the column unit
                    rsafe = safe_new[b, :Ct]
                    blk = mbar[ck][b][rsafe][:, qj] * mbar[gk][b][jj][None, :]
                    ob = ob.at[:Ct, s:e].add(hp_rows[b, :Ct, None] * blk)
                    continue
                # diag / theta: exactly one nonzero per column — scatter
                p = inv[qj]
                valid = p >= 0
                if kind == _THETA:
                    val = -hp_full[b, qj]
                else:
                    val = (hp_full[b, qj] * mbar[ck][b][qj]
                           * mbar[gk][b][jj])
                ob = ob.at[jnp.where(valid, p, 0),
                           jnp.arange(s, e)].add(jnp.where(valid, val, 0.0))
            return ob.astype(vals.dtype)
        return branch

    outs = []
    for b in range(B):
        cb = jnp.maximum(jnp.maximum(count_new[b], count_prev[b]), 1)
        cb = jnp.minimum(cb, K)
        sel = sum((cb > r).astype(jnp.int32) for r in ladder[:-1])
        outs.append(lax.switch(sel, [body(Ct, b) for Ct in ladder]))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: ragged @pl.when grid skips over XLA-gathered J tiles
# ---------------------------------------------------------------------------

def _fused_kernel(cnt_new_ref, cnt_prev_ref, J_ref, vals_ref, mbar_ref,
                  hp_ref, out_ref, *, bk: int, bl: int, nlb: int):
    b = pl.program_id(0)
    row_base = pl.program_id(1) * bk

    @pl.when(row_base >= cnt_new_ref[b])
    def _dead():                       # ragged per-example row-block skip
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(row_base < cnt_new_ref[b])
    def _live():
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
        for lb in range(nlb):          # ragged prev-row blocks
            def contract(a, lb=lb):
                Jt = J_ref[0, :, lb * bl:(lb + 1) * bl]            # [bk, bl]
                vblk = vals_ref[0, lb * bl:(lb + 1) * bl, :].astype(
                    jnp.float32)                                   # [bl, bp]
                return a + lax.dot(Jt, vblk, precision=lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
            acc = lax.cond(lb * bl < cnt_prev_ref[b], contract,
                           lambda a: a, acc)
        acc = acc + mbar_ref[0].astype(jnp.float32)
        out_ref[0] = (hp_ref[0] * acc).astype(out_ref.dtype)       # hp [bk, 1]


@functools.partial(jax.jit,
                   static_argnames=("bk", "bl", "bp", "interpret"))
def fused_update_pallas(Jgg, vals, mbar_rows, hp_rows, count_new, count_prev,
                        *, bk: int = 8, bl: int = 8, bp: int = 128,
                        interpret: bool | None = None):
    """One fused dual-compact influence update on the TPU grid.

    Jgg [B, K, K] f32 J-hat tiles at (new rows, prev rows) from
    `compact.gather_j_tiles` (dead slots read 0); vals [B, K, Pc_pad]
    compact carry (f32 or bf16); mbar_rows [B, K, Pc_pad] M-bar gathered at
    the new active rows (hp-ungated); hp_rows [B, K] with dead slots
    zeroed; count_new/count_prev [B] (scalar-prefetched).  Returns the new
    carry in vals.dtype.

    Grid (B, K/bk, Pc_pad/bp); row blocks beyond count_new[b] and prev-row
    blocks beyond count_prev[b] are skipped per example, so executed MXU
    work is Sigma_b K_b K'_b Pc — see the module docstring for the mapping
    onto the paper's cost terms.  The block dots run at HIGHEST precision
    (f32 contraction on the MXU): Mosaic would otherwise be free to pick a
    bf16 pass, and the engine claims exact gradients.  interpret: None =
    compiled Mosaic on a TPU, the Pallas interpreter elsewhere."""
    B, K, Pc_pad = vals.shape
    if Jgg.shape != (B, K, K):
        raise ValueError(f"Jgg {Jgg.shape} must be the [B, K, K] = "
                         f"{(B, K, K)} tiles of the carry")
    if K % bk or K % bl or Pc_pad % bp:
        raise ValueError(f"fused kernel tiling needs K % {bk} == K % {bl} "
                         f"== 0 and Pc_pad % {bp} == 0; got K={K}, "
                         f"Pc_pad={Pc_pad}")
    interpret = (not _on_tpu()) if interpret is None else interpret
    kernel = functools.partial(_fused_kernel, bk=bk, bl=bl, nlb=K // bl)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K // bk, Pc_pad // bp),
            in_specs=[
                pl.BlockSpec((1, bk, K), lambda b, kb, pb, *_: (b, kb, 0)),
                pl.BlockSpec((1, K, bp), lambda b, kb, pb, *_: (b, 0, pb)),
                pl.BlockSpec((1, bk, bp), lambda b, kb, pb, *_: (b, kb, pb)),
                # hp as a [B, K, 1] column: (bk, 1) obeys the (8, 128)
                # block rule (1 is the full last dim), a (1, bk) row does not
                pl.BlockSpec((1, bk, 1), lambda b, kb, pb, *_: (b, kb, 0)),
            ],
            out_specs=pl.BlockSpec((1, bk, bp),
                                   lambda b, kb, pb, *_: (b, kb, pb)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, Pc_pad), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(count_new, count_prev, Jgg.astype(jnp.float32), vals, mbar_rows,
      hp_rows[:, :, None].astype(jnp.float32))


def fused_reference(Jgg, vals, mbar_rows, hp_rows, count_new, count_prev,
                    *, bl: int = 8):
    """Pure-jnp oracle with the KERNEL's blockwise accumulation order
    (l blocks of bl, ascending), so f32 parity with interpret-mode
    `fused_update_pallas` is bitwise: summing a dead block's exact zeros
    is the identity, and live blocks add in the same order."""
    B, K, Pc_pad = vals.shape
    acc = jnp.zeros((B, K, Pc_pad), jnp.float32)
    for lb in range(K // bl):
        blk = jnp.einsum("bkl,blp->bkp", Jgg[:, :, lb * bl:(lb + 1) * bl],
                         vals[:, lb * bl:(lb + 1) * bl].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        live = (lb * bl < count_prev).astype(jnp.float32)[:, None, None]
        acc = acc + blk * live
    out = hp_rows[:, :, None] * (acc + mbar_rows.astype(jnp.float32))
    krow = jnp.arange(K)[None, :, None]
    out = jnp.where(krow < jnp.minimum(count_new, K)[:, None, None], out, 0.0)
    return out.astype(vals.dtype)
