"""Error-feedback int8 gradient compression for cross-pod (DCN) all-reduce.

The slow axis in a multi-pod job is the data-center network between pods;
the classic mitigation is quantized all-reduce with error feedback:

    q = quantize_int8(g + e)          # e: residual carried across steps
    g_hat = psum(q) * scale           # int8 on the wire (4x fewer bytes)
    e'   = (g + e) - dequant(q)       # feedback keeps the update unbiased
                                      # over time (compression error decays)

Implemented as a shard_map over the 'pod' axis with GSPMD left automatic on
the other axes (auto=... partial-manual), so the intra-pod sharding of the
gradient tree is untouched and only the pod-axis reduction is quantized.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Tree = Any


def _quant_int8(x: jax.Array):
    """Symmetric per-tensor int8 quantization -> (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_int8_allreduce(g: jax.Array, err: jax.Array, axis_name: str):
    """One error-feedback compressed all-reduce step (inside shard_map).

    Returns (g_hat averaged over axis, new_err)."""
    x = g.astype(jnp.float32) + err
    q, scale = _quant_int8(x)
    # int8 summed in int32 on the wire; scales reduced separately (max)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_max = jax.lax.pmax(scale, axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    g_hat = qsum.astype(jnp.float32) * scale_max / n
    new_err = x - q.astype(jnp.float32) * scale
    return g_hat, new_err


def compressed_psum(grads: Tree, err: Tree, mesh: Mesh,
                    axis_name: str = "pod"):
    """Tree-level compressed mean over `axis_name` with error feedback.

    grads are assumed identical in sharding over the non-pod axes; only the
    pod reduction goes through int8."""
    flat, treedef = jax.tree.flatten(grads)
    flat_err = treedef.flatten_up_to(err)

    specs = tuple(P() for _ in flat)

    # full-manual over the mesh; P() = replicated view per device.  Used in
    # the pure-DP-across-pods mode where grads are already reduced in-pod.
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, specs), out_specs=(specs, specs),
        check_vma=False)
    def go(gs, es):
        outs = [ef_int8_allreduce(g, e, axis_name) for g, e in zip(gs, es)]
        return tuple(o[0] for o in outs), tuple(o[1] for o in outs)

    g_hat, new_err = go(tuple(flat), tuple(flat_err))
    return treedef.unflatten(list(g_hat)), treedef.unflatten(list(new_err))


def init_error_state(grads_abstract: Tree) -> Tree:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_abstract)
