"""Pallas TPU kernel: chunked RWKV6 WKV with the state resident in VMEM.

The XLA chunked path (models/rwkv.py::wkv_full) spills the [D,D] state and the
[L,L,D] joint-exponent tensor to HBM every chunk; this kernel keeps both in
VMEM across the whole sequence:

  grid = (B*H, T/L)  with dimension_semantics ("parallel", "arbitrary") —
  the chunk axis is sequential, so the f32 state scratch carries over between
  chunk steps of the same (batch, head) program.  HBM traffic collapses to
  the r/k/v/w tiles in and o tiles out (the `mem_fused` bound in
  EXPERIMENTS.md §Roofline).

Math is identical to wkv_chunk (same clamped joint-exponent trick); validated
in interpret mode against ref.wkv_chunk_ref chained over chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, logw_ref, u_ref, o_ref, s_ref, *, L: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)              # [L, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = logw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)              # [D]
    S = s_ref[...]                                # [D, D] carried state

    logP = jnp.cumsum(logw, axis=0)
    logP_prev = logP - logw

    # inter-chunk: (r_i * exp(logP_{i-1})) @ S
    q_inter = r * jnp.exp(logP_prev)
    o_inter = jax.lax.dot(q_inter, S, preferred_element_type=jnp.float32)

    # intra-chunk: joint clamped exponent on the [L, L, D] 3-tensor
    delta = jnp.minimum(logP_prev[:, None, :] - logP[None, :, :], 0.0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    diag = (ii == jj)[..., None]
    tri = (ii > jj)[..., None]
    w_pair = jnp.where(diag, u[None, None, :], jnp.exp(delta))
    w_pair = jnp.where(tri | diag, w_pair, 0.0)
    A = jnp.einsum("id,ijd,jd->ij", r, w_pair, k,
                   preferred_element_type=jnp.float32)
    o_intra = jax.lax.dot(A, v, preferred_element_type=jnp.float32)

    o_ref[0] = (o_inter + o_intra).astype(o_ref.dtype)

    # state update: S <- diag(exp(logP_L)) S + sum_j (k_j e^{logP_L - logP_j}) v_j^T
    logP_L = logP[-1:, :]                        # [1, D]
    k_tail = k * jnp.exp(logP_L - logP)          # [L, D]
    s_ref[...] = (jnp.exp(logP_L[0])[:, None] * S
                  + jax.lax.dot(k_tail.T, v,
                                preferred_element_type=jnp.float32))


def wkv_pallas(r, k, v, logw, u, *, chunk: int = 16, interpret: bool = None):
    """r/k/v: [B, H, T, D] (bf16/f32); logw: [B, H, T, D] f32 (<= 0);
    u: [H, D] f32.  Returns o: [B, H, T, D] (f32).

    T % chunk == 0 (pad upstream); D should be a multiple of 128 on real TPUs
    (any D works in interpret mode)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, T, D = r.shape
    assert T % chunk == 0, (T, chunk)
    BH, L = B * H, chunk
    fold = lambda x: x.reshape(BH, T, x.shape[-1])
    r2, k2, v2, w2 = fold(r), fold(k), fold(v), fold(logw)
    u2 = jnp.broadcast_to(u[None], (B, H, D)).reshape(BH, D)

    out = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=(BH, T // L),
        in_specs=[
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),   # r
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),   # k
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),   # v
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),   # logw
            pl.BlockSpec((1, D), lambda bh, c: (bh, 0)),         # u
        ],
        out_specs=pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r2, k2, v2, w2, u2)
    return out.reshape(B, H, T, D)
