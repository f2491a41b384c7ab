"""Pallas TPU kernel: block-sparse RTRL influence-matrix update.

    out[b] = D(hp[b]) . ( J-hat[b] @ M[b] + M-bar[b] )        (paper Eq. 10)

M/Mbar are in the FLAT layout (`repro.core.sparse_rtrl.FlatLayout`): every
gate's (q, m) parameter columns concatenated into one lane-padded [B, n, P]
buffer, so a single kernel invocation per step covers all gates of the EGRU
cell — this is the `backend="pallas"` hot path of
`sparse_rtrl_loss_and_grads`.

This is THE compute hot-spot of RTRL (O(n^2 p) per step).  The TPU
adaptation (DESIGN.md §3) realises the paper's four sparsity factors at
block granularity via scalar-prefetched masks:

  1. beta(t)   — output row-blocks with H'(v)=0 are skipped entirely
                 (@pl.when on the whole block: no matmul, zeros written);
  2. beta(t-1) — the contraction over l skips l-blocks whose M rows are zero
                 (per-block lax.cond inside the accumulation loop);
  3. omega (columns) — parameter-column blocks pruned by the fixed mask are
                 skipped (their M columns are permanently zero);
  4. omega (J)  — J inherits W_rec's block-sparsity pattern, so (k,l) blocks
                 with an all-zero mask are skipped inside the loop.

VMEM tiling: J row-block [bk, n] stays resident across the p-grid; M is
streamed as [bl, bp] tiles; the MXU sees only dense [bk, bl] x [bl, bp]
products, all dims multiples of (8, 128) by padding in ops.py.

DUAL-COMPACT mode (combined activity x parameter sparsity): the kernel is
width-agnostic in P, so the `backend="pallas"` engine can feed it M/Mbar
carried COLUMN-compact at Pc_pad ~= w~ P (`sparse_rtrl.ColLayout`; Mbar
built directly at compact width by `flat_mbar_cols`).  The w~ p-side factor
is then physical — the p-grid itself is w~ shorter, instead of relying on
factor 3 to skip dead column blocks — while factor 4 (jmask) still prunes
the R-blocks of the J contraction, the w~ factor on the n^2 side.  col_mask
degenerates to the pad-block indicator.  Lane alignment is preserved because
ColLayout pads Pc to a LANE (= bp) multiple.

Validated in interpret mode on CPU against `repro.kernels.ref.influence_ref`
over shape/dtype/sparsity sweeps (tests/test_kernels.py).

This kernel skips dead blocks of a DENSE [B, n, P] carry.  Its successor,
`repro.kernels.compact_fused`, instead carries the ROW-compact [B, K, Pc]
buffer of compact.py and fuses the J-tile gather, the [K x K'] x [K' x Pc]
contraction, the M-bar add and the hp scale into one invocation with ragged
per-example capacity — see its module docstring for how each grid axis maps
to a factor of the paper's  w~ b~(t) b~(t-1) n^2 p  cost term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_mask_ref, prev_mask_ref, col_mask_ref, jmask_ref,
            hp_ref, J_ref, M_ref, Mbar_ref, out_ref, *, bl: int, nlb: int):
    b = pl.program_id(0)
    kb = pl.program_id(1)
    pb = pl.program_id(2)

    active = (row_mask_ref[b, kb] != 0) & (col_mask_ref[pb] != 0)

    @pl.when(jnp.logical_not(active))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active)
    def _():
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)   # [bk, bp]
        for lb in range(nlb):                      # static unroll over l-blocks
            pred = (prev_mask_ref[b, lb] != 0) & (jmask_ref[kb, lb] != 0)

            def compute(acc, _lb=lb):
                j_blk = J_ref[0, :, _lb * bl:(_lb + 1) * bl]      # [bk, bl]
                m_blk = M_ref[0, _lb * bl:(_lb + 1) * bl, :]      # [bl, bp]
                return acc + jax.lax.dot(
                    j_blk, m_blk, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)

            acc = jax.lax.cond(pred, compute, lambda a: a, acc)
        acc = acc + Mbar_ref[0]
        hpv = hp_ref[0]                                   # [bk, 1]
        out_ref[0] = (hpv * acc).astype(out_ref.dtype)


def influence_update_pallas(hp, Jhat, M, Mbar, *, row_mask, prev_mask,
                            col_mask, jmask, bk=8, bl=8, bp=128,
                            interpret=False):
    """hp: [B,n]; Jhat: [B,n,n]; M/Mbar: [B,n,P] (pre-padded, P % bp == 0).

    Masks are int32 block-activity indicators:
      row_mask [B, n/bk], prev_mask [B, n/bl], col_mask [P/bp],
      jmask [n/bk, n/bl].
    """
    B, n, P = M.shape
    assert n % bk == 0 and n % bl == 0 and P % bp == 0, (n, P, bk, bl, bp)
    nkb, nlb, npb = n // bk, n // bl, P // bp

    grid = (B, nkb, npb)
    kernel = functools.partial(_kernel, bl=bl, nlb=nlb)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                # hp as a [B, n, 1] column: (bk, 1) obeys the (8, 128)
                # block rule, a (1, bk) row block does not
                pl.BlockSpec((1, bk, 1), lambda b, kb, pb, *_: (b, kb, 0)),  # hp
                pl.BlockSpec((1, bk, n), lambda b, kb, pb, *_: (b, kb, 0)),  # Jhat
                pl.BlockSpec((1, n, bp), lambda b, kb, pb, *_: (b, 0, pb)),  # M
                pl.BlockSpec((1, bk, bp), lambda b, kb, pb, *_: (b, kb, pb)),# Mbar
            ],
            out_specs=pl.BlockSpec((1, bk, bp), lambda b, kb, pb, *_: (b, kb, pb)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, n, P), M.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(row_mask, prev_mask, col_mask, jmask, hp[:, :, None], Jhat, M, Mbar)
    return out


def block_any(x: jax.Array, block: int, axis: int) -> jax.Array:
    """Block-activity indicator along `axis` (int32 0/1)."""
    shape = list(x.shape)
    n = shape[axis]
    nb = n // block
    shape[axis:axis + 1] = [nb, block]
    xr = x.reshape(shape)
    return jnp.any(xr != 0, axis=axis + 1).astype(jnp.int32)


def build_block_masks(hp_p, M_p, col_mask, jmask, *, bk: int, bl: int,
                      bp: int):
    """Derive the four per-step block-activity masks the kernel prefetches.

    Inputs are already padded to tile multiples (hp_p [B, n_p], M_p
    [B, n_p, P_p]); col_mask is the [P] parameter-column liveness and jmask
    the [n, n] J pattern (both optional, unpadded).  Returns int32
    (row_mask [B, n_p/bk], prev_mask [B, n_p/bl], col_blocks [P_p/bp],
    j_blocks [n_p/bk, n_p/bl])."""
    n_p, P_p = M_p.shape[1], M_p.shape[2]
    row_mask = block_any(hp_p, bk, axis=1)
    prev_mask = block_any(jnp.any(M_p != 0, axis=2).astype(jnp.int32),
                          bl, axis=1)
    if col_mask is None:
        col_blocks = jnp.ones((P_p // bp,), jnp.int32)
    else:
        cm = col_mask.astype(jnp.int32)
        cm = jnp.pad(cm, (0, P_p - cm.shape[0]))
        col_blocks = block_any(cm[None], bp, axis=1)[0]
    if jmask is None:
        j_blocks = jnp.ones((n_p // bk, n_p // bl), jnp.int32)
    else:
        jmT = jmask.T.astype(jnp.int32)                     # [k, l]
        jmT = jnp.pad(jmT, ((0, n_p - jmT.shape[0]), (0, n_p - jmT.shape[1])))
        j_blocks = jnp.any(
            jmT.reshape(n_p // bk, bk, n_p // bl, bl) != 0,
            axis=(1, 3)).astype(jnp.int32)
    return row_mask, prev_mask, col_blocks, j_blocks
