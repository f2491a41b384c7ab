"""The head-diagonal trace update of a Mamba-2 mixer's exact RTRL, in one
pass over the traces.

Each trace of the learned mixer's local heads is `T[b, h, p, n, ...]`,
dh_t[h, p, n] / dtheta (`repro.cells.mamba2`).  One step reads T, writes

    T' = d[b, h] T + increment

in place (d = exp(dt A) of the head, 0 on a row whose document starts),
and contracts lam[b, h, p, n] = dL_t/dh_t against T' into the step's
gradient of each learned leaf.  The three big traces ([B, Hl, P, N, D]:
the x, B and dt rows of in_proj) take their increments as rank-1 factors,
never as a trace-sized array:

    x   fx[b,h,p,n] * ux[b,h,p,j]      -> grad [Hl*P, D]  (sum over b, n)
    B   fB[b,h,p]   * uB[b,n,j]        -> grad [N, D]     (sum over b, h, p)
    dt  g[b,h,p,n]  * u[b,j]           -> grad [Hl, D]    (sum over b, p, n)

and the small ones (conv channels, A_log, dt_bias) whole.  Written as one
XLA program: the compiler fuses each big trace's read, update, write and
contraction into one loop over it (`tests/test_tpu_compile.py` checks the
program a v5e compiles), and the chunk donates the traces, so the update
happens in place.
"""
from __future__ import annotations

import jax.numpy as jnp

# per small leaf: the axes of [B, Hl, P, N, ...] the gradient sums over,
# and whether the leaf's rows are the (h, p) pairs
_SMALL = {"conv_x": ((0, 3), True), "conv_x_b": ((0, 3), True),
          "conv_B": ((0, 1, 2), False), "conv_B_b": ((0, 1, 2), False),
          "A_log": ((0, 2, 3), False), "dt_bias": ((0, 2, 3), False)}


def ssm_trace_update(tr: dict, decay, inc: dict, lam) -> tuple[dict, dict]:
    """-> (traces', gradient of the step): tr as `cells.mamba2.init_traces`,
    decay [B, Hl], inc as `cells.mamba2.trace_increments`, lam [B, Hl, P,
    N]."""
    B, Hl, P, N = lam.shape
    d = decay[:, :, None, None, None]
    lam5 = lam[..., None]
    fx, ux = inc["x"]
    fB, uB = inc["B"]
    g, u = inc["dt"]
    new = {"x": d * tr["x"] + fx[..., None] * ux[:, :, :, None, :],
           "B": d * tr["B"] + fB[..., None, None] * uB[:, None, None],
           "dt": d * tr["dt"] + g[..., None] * u[:, None, None, None]}
    D = u.shape[-1]
    grads = {"x": jnp.sum(lam5 * new["x"], axis=(0, 3)).reshape(Hl * P, D),
             "B": jnp.sum(lam5 * new["B"], axis=(0, 1, 2)),
             "dt": jnp.sum(lam5 * new["dt"], axis=(0, 2, 3))}
    for k, (axes, rows) in _SMALL.items():
        t = tr[k]
        dk = decay.reshape(decay.shape + (1,) * (t.ndim - 2))
        new[k] = dk * t + inc[k]
        lk = lam.reshape(lam.shape + (1,) * (t.ndim - 4))
        gk = jnp.sum(lk * new[k], axis=axes)
        grads[k] = gk.reshape((Hl * P,) + gk.shape[2:]) if rows else gk
    return new, grads
