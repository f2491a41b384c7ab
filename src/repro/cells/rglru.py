"""RG-LRU as a zoo cell: diagonal recurrence, EXACT O(n·p) RTRL.

The Griffin / RecurrentGemma recurrence (models/rglru.py runs it at model
scale with an associative scan)

    r_t = sigmoid(x_t Wa)          i_t = sigmoid(x_t Wi)
    a_t = exp(-c · r_t · softplus(lam))
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ (x_t Wx))

has a DIAGONAL state Jacobian J_t = diag(a_t), so the paper's influence
recursion M_t = D(hp)[J M + Mbar] factors into independent per-parameter
eligibility traces

    e_t[w] = a_t ⊙ e_{t-1}[w] + dh_t/dw|_{h_{t-1} fixed}

— O(n_in·n) trace memory and O(n·p) update FLOPs per step, no [B, K, P]
influence buffer and no n² Jacobian factor at all.  `engine="diag_exact"`
(repro.core.learner.DiagExactLearner) carries exactly this; grads are exact
(verified vs BPTT in tests/test_cells.py).

:class:`DiagCell` wraps the older toy diagonal cell (`repro.core.diag_rtrl`,
no input gate) in the same protocol so `engine="diag"` dispatches through it
unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Tree = Any


@dataclasses.dataclass(frozen=True)
class RGLRUCellConfig:
    n: int = 64                  # state width
    n_in: int = 32
    n_out: int = 4
    c: float = 8.0               # recurrence-gate exponent (Griffin)

    def replace(self, **kw) -> "RGLRUCellConfig":
        return dataclasses.replace(self, **kw)

    @property
    def n_rec_params(self) -> int:
        return 3 * self.n_in * self.n + self.n


def init_params(cfg: RGLRUCellConfig, key) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s = 1.0 / jnp.sqrt(cfg.n_in)
    return {
        "Wx": s * jax.random.normal(k1, (cfg.n_in, cfg.n)),   # input proj
        "Wa": s * jax.random.normal(k2, (cfg.n_in, cfg.n)),   # recurrence gate
        "Wi": s * jax.random.normal(k3, (cfg.n_in, cfg.n)),   # input gate
        "lam": jax.random.uniform(k4, (cfg.n,), minval=2.2, maxval=5.5),
        "out": {"W": (1.0 / jnp.sqrt(cfg.n)) *
                jax.random.normal(k5, (cfg.n, cfg.n_out)),
                "b": jnp.zeros((cfg.n_out,))},
    }


def gates(cfg: RGLRUCellConfig, params, x_t):
    """-> (a, scale, i, r, xw): everything the step and the traces share."""
    r = jax.nn.sigmoid(x_t @ params["Wa"])
    i = jax.nn.sigmoid(x_t @ params["Wi"])
    a = jnp.exp(-cfg.c * r * jax.nn.softplus(params["lam"]))
    scale = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-9))
    xw = x_t @ params["Wx"]
    return a, scale, i, r, xw


def step(cfg: RGLRUCellConfig, params, h, x_t):
    """Plain autodiff-able step — what the BPTT oracle differentiates."""
    a, scale, i, _, xw = gates(cfg, params, x_t)
    return a * h + scale * (i * xw)


def cell_partials(cfg: RGLRUCellConfig, params, h_prev, x_t):
    """Closed-form (h_new, hp, a-diag [B,n], mbar) — the diagonal-Jacobian
    analogue of the EGRU `cell_partials`: J_t = diag(a_t) and mbar[w] =
    dh_t/dw with h_{t-1} held fixed, one leaf per recurrent parameter
    tensor with the state axis n trailing."""
    r = jax.nn.sigmoid(x_t @ params["Wa"])
    i = jax.nn.sigmoid(x_t @ params["Wi"])
    sp = jax.nn.softplus(params["lam"])
    a = jnp.exp(-cfg.c * r * sp)
    scale = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-9))
    xw = x_t @ params["Wx"]
    xb = i * xw
    h_new = a * h_prev + scale * xb
    # through the gate a:  dh/da = h_prev + (dscale/da) xb,  dscale/da=-a/scale
    ha = h_prev + (-a / scale) * xb                            # [B,n]
    dr = r * (1.0 - r)
    da_dWa = a * (-cfg.c * sp) * dr                            # coef on x_j
    da_dlam = a * (-cfg.c * r) * jax.nn.sigmoid(params["lam"])
    di = i * (1.0 - i)
    mbar = {
        "Wx": (scale * i)[:, None, :] * x_t[:, :, None],
        "Wi": (scale * xw * di)[:, None, :] * x_t[:, :, None],
        "Wa": (ha * da_dWa)[:, None, :] * x_t[:, :, None],
        "lam": ha * da_dlam,
    }
    hp = jnp.ones_like(a)       # no activity gate: every row live
    return h_new, hp, a, mbar


def init_traces(cfg: RGLRUCellConfig, batch: int) -> dict:
    """e[w] = dh/dw: [B, n_in, n] per projection, [B, n] for lam — total
    O(B n_in n) = O(B p/3), the whole trace state (no n² factor)."""
    z2 = jnp.zeros((batch, cfg.n_in, cfg.n))
    return {"Wx": z2, "Wi": z2, "Wa": z2, "lam": jnp.zeros((batch, cfg.n))}


def make_masks(cfg: RGLRUCellConfig, key, sparsity: float) -> dict:
    """Fixed parameter masks over the projections (lam stays dense, like
    bias/theta in the EGRU convention)."""
    ks = jax.random.split(key, 3)
    def bern(k):
        return (jax.random.uniform(k, (cfg.n_in, cfg.n))
                >= sparsity).astype(jnp.float32)
    return {"Wx": bern(ks[0]), "Wi": bern(ks[1]), "Wa": bern(ks[2]),
            "lam": jnp.ones((cfg.n,))}


def apply_masks(params: dict, masks: dict) -> dict:
    out = dict(params)
    for k, m in masks.items():
        out[k] = params[k] * m
    return out


def bptt_loss_and_grads(cfg: RGLRUCellConfig, params, xs, labels):
    """Reverse-mode BPTT oracle: loss = mean_t CE(h_t W_out + b, labels)."""
    T, B, _ = xs.shape

    def loss_fn(params):
        def body(h, x_t):
            h = step(cfg, params, h, x_t)
            return h, h
        _, hs = jax.lax.scan(body, jnp.zeros((B, cfg.n)), xs)
        logits = hs @ params["out"]["W"] + params["out"]["b"]    # [T,B,o]
        ls = jax.nn.log_softmax(logits, -1)
        lab = jnp.broadcast_to(jnp.maximum(labels, 0)[None, :, None],
                               (T, B, 1))
        return -jnp.mean(jnp.take_along_axis(ls, lab, 2))

    return jax.value_and_grad(loss_fn)(params)


class DiagonalExact:
    """The two halves of a `DiagExactLearner` step for a jac_kind="diagonal"
    cell (J_t = diag(a_t), a_t [B, n]): `exact_terms` gives the partials
    (masked increments) and the readout's reverse mode, lam = dL_t/dh_t,
    with no immediate term on the recurrent parameters; `trace_update`
    applies e_t[w] = a_t * e_{t-1}[w] + mbar_t[w] and contracts lam."""

    def exact_terms(self, params, frozen, h, x_t, y_t, tt, masks=None):
        """-> (h', a_t, mbar, lam, None, g_out, loss, logits, stats)."""
        w = self.rec_params(params)
        h_new, hp, adiag, mbar = self.partials(w, h, x_t)
        if masks is not None:
            mbar = jax.tree.map(lambda m, mk: m * mk, mbar, masks)

        def inst_loss(po, hi):
            logits = self.readout({"out": po}, hi)
            lab = jnp.maximum(y_t, 0)
            ls = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(ls, lab[:, None], 1)) / tt

        lt, (gout_t, cbar) = jax.value_and_grad(inst_loss, argnums=(0, 1))(
            params["out"], h_new)
        return (h_new, adiag, mbar, cbar, None, gout_t, lt,
                self.readout(params, h_new), {})

    @staticmethod
    def trace_update(tr, adiag, mbar, cbar):
        """a_t [B, n] broadcast over each leaf [B, ..., n]; -> (traces',
        the step's gradient cbar^T e_t)."""
        def decay(leaf):
            shape = ((adiag.shape[0],) + (1,) * (leaf.ndim - 2)
                     + (adiag.shape[-1],))
            return jnp.reshape(adiag, shape)

        tr_new = jax.tree.map(lambda t, m: decay(t) * t + m, tr, mbar)
        gw_t = jax.tree.map(
            lambda e: jnp.einsum("bk,b...k->...k", cbar, e), tr_new)
        return tr_new, gw_t


class RGLRUCell(DiagonalExact):
    """RG-LRU behind the pluggable cell protocol: jac_kind="diagonal", so
    the third `partials` output is the diagonal a_t [B, n], not a [B, n, n]
    Jacobian, and mbar is the per-parameter trace increment tree."""

    name = "rglru"
    jac_kind = "diagonal"

    def __init__(self, cfg: RGLRUCellConfig):
        self.cfg = cfg

    def init_params(self, key) -> Tree:
        return init_params(self.cfg, key)

    def rec_params(self, params: Tree) -> Tree:
        return {k: v for k, v in params.items() if k != "out"}

    def init_state(self, batch: int) -> jax.Array:
        return jnp.zeros((batch, self.cfg.n))

    def init_traces(self, batch: int) -> Tree:
        return init_traces(self.cfg, batch)

    def partials(self, w: Tree, h_prev: jax.Array, x_t: jax.Array):
        return cell_partials(self.cfg, w, h_prev, x_t)

    def step_st(self, w: Tree, h_prev: jax.Array, x_t: jax.Array):
        return step(self.cfg, w, h_prev, x_t)

    def readout(self, params: Tree, h: jax.Array) -> jax.Array:
        return h @ params["out"]["W"] + params["out"]["b"]

    def activity_mask(self, h: jax.Array) -> jax.Array:
        return h != 0.0


class DiagCell(DiagonalExact):
    """The original toy diagonal cell (`repro.core.diag_rtrl`, no input
    gate) behind the same protocol — `engine="diag"` dispatches through this
    adapter; carry structure and trace math are the historical ones."""

    name = "diag"
    jac_kind = "diagonal"

    def __init__(self, cfg):
        self.cfg = cfg              # diag_rtrl.DiagCellConfig

    def init_params(self, key) -> Tree:
        from repro.core import diag_rtrl as D
        return D.init_params(self.cfg, key)

    def rec_params(self, params: Tree) -> Tree:
        return {k: v for k, v in params.items() if k != "out"}

    def init_state(self, batch: int) -> jax.Array:
        return jnp.zeros((batch, self.cfg.n))

    def init_traces(self, batch: int) -> Tree:
        from repro.core import diag_rtrl as D
        return D.init_traces(self.cfg, batch)

    def partials(self, w: Tree, h_prev: jax.Array, x_t: jax.Array):
        from repro.core import diag_rtrl as D
        return D.cell_partials(self.cfg, w, h_prev, x_t)

    def step_st(self, w: Tree, h_prev: jax.Array, x_t: jax.Array):
        from repro.core import diag_rtrl as D
        return D.step(self.cfg, w, h_prev, x_t)

    def readout(self, params: Tree, h: jax.Array) -> jax.Array:
        return h @ params["out"]["W"] + params["out"]["b"]

    def activity_mask(self, h: jax.Array) -> jax.Array:
        return h != 0.0
