"""Plain reference of online exact RTRL for a one-layer EGRU, with AdamW.

Written from the paper's equations (arXiv:2303.05641, Secs. 4-6) in plain
`jax.numpy`; it imports nothing of the program under test.

Online exact RTRL carries d a_t / d theta across update windows while the
parameters change between windows.  That influence is the derivative of a_t
with respect to one perturbation delta added to the parameters of EVERY step
since the stream began (each step using the parameters of its own window).
So the gradient of window w is computed here by reverse-mode differentiation
of the window's loss through the whole history from step 0, with delta
shared by all steps: no influence matrix at all, only the forward equations
and autodiff.  The Heaviside's derivative is the paper's pseudo-derivative
gamma * max(0, 1 - |v| / (2 eps)) (straight-through).

Parameters are a flat dict of the canonical leaf names ("u.W", "u.R",
"u.b", the same for "r" and "z", "theta", "out.W", "out.b").  Masks hold the
kept-weight pattern of each masked leaf; the masked gradient and the masked
parameters follow the fixed-sparsity rule of the paper's Sec. 5.

`matmul` selects the arithmetic: "highest" (the input dtype, exact passes),
or "bf16x3" (each f32 operand split into a bf16 high and low part and three
products summed in f32: XLA:TPU's `high` precision, written out so that it
means the same on every backend).

A run may start from a saved state instead of the zero state (`start`):
the parameters, AdamW's m and v and its update count, the recurrent state
a and the influence M = d a / d theta at the first step, in this module's
dense layout ({leaf: [B, n, *leaf shape]}).  The gradient of a window is
then two terms added together: reverse mode through the steps since the
start, with a held fixed, and lambda^T M, where lambda = d L_window / d a
at the start comes from the same reverse pass.  Both are exact; with M = 0
and the zero state they are the run from the zero state.

The Heaviside makes a unit's event depend on the sign of its
pre-activation v.  Where the reference finds |v| under `TIE_MARGIN`,
float32 rounding may decide the event either way, and both outcomes are
exact results: `alternatives` gives the runs with one such event flipped
(`alt`), and the check judges a stream against whichever it matches best.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GATES = ("u", "r", "z")
INPUTS = ("xs", "ys")   # a stream's input arrays, by their record names
TIE_MARGIN = 1e-5     # |v| under this: float32 may take the event either way
MAX_TIES = 4          # near-ties tried per stream


def _mm(matmul: str):
    if matmul == "highest":
        return lambda a, b: jnp.matmul(a, b,
                                       precision=jax.lax.Precision.HIGHEST)
    if matmul == "bf16x3":
        def mm(a, b):
            f32 = jnp.float32
            a, b = a.astype(f32), b.astype(f32)
            a_hi = a.astype(jnp.bfloat16)
            b_hi = b.astype(jnp.bfloat16)
            a_lo = (a - a_hi.astype(f32)).astype(jnp.bfloat16)
            b_lo = (b - b_hi.astype(f32)).astype(jnp.bfloat16)
            dot = functools.partial(jnp.matmul, preferred_element_type=f32)
            return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)
        return mm
    raise ValueError(f"unknown matmul mode {matmul!r}")


def _heaviside_st(gamma: float, eps: float):
    """Heaviside forward (XOR a flip pattern), pseudo-derivative backward."""
    @jax.custom_jvp
    def h(v, flip):
        return jnp.logical_xor(v > 0, flip).astype(v.dtype)

    @h.defjvp
    def h_jvp(primals, tangents):
        v, flip = primals
        dv, _ = tangents
        hp = gamma * jnp.maximum(0.0, 1.0 - jnp.abs(v) / (2.0 * eps))
        return h(v, flip), hp * dv
    return h


def make_cell(model: dict, matmul: str = "highest"):
    """One EGRU step, `cell(p, a, x, flip) -> (a_new, v)`: the events a_new
    and the pre-activations v, from the canonical leaves p."""
    mm = _mm(matmul)
    h = _heaviside_st(float(model["gamma"]), float(model["eps"]))

    def cell(p, a, x, flip):
        u = jax.nn.sigmoid(mm(x, p["u.W"]) + mm(a, p["u.R"]) + p["u.b"])
        r = jax.nn.sigmoid(mm(x, p["r.W"]) + mm(a, p["r.R"]) + p["r.b"])
        z = jnp.tanh(mm(x, p["z.W"]) + mm(r * a, p["z.R"]) + p["z.b"])
        v = u * z + (1.0 - u) * a - p["theta"]
        return h(v, flip), v
    return cell


def make_reference(model: dict, windows: int, matmul: str = "highest",
                   drop_half_batch: bool = False):
    """Build `run(shared, stream)` for one stream.

    model: the configuration's cell and optimizer entries (n_hidden, gamma,
    eps, update_every, lr, b1, b2, adam_eps).  shared: {"params0", "masks"}
    (canonical leaves).  stream: {"xs" [windows*k, B, n_in], "ys"
    [windows*k, B] int32}, and optionally "start" (see the module
    docstring: {"params", "m", "v", "count", "state", "influence"}) and
    "alt" [windows*k, B, n] bool (events flipped where a near-tie is
    resolved the other way).  Returns a dict: `loss` [windows] (each
    window's loss before its update), `grad1` (the masked gradient of the
    first window), `params`, `m`, `v` and `state` after `windows` updates,
    and `v_pre` [windows*k, B, n] (the pre-activations along the
    trajectory).

    drop_half_batch plants a fault for the benchmark's own checks: every
    loss is the mean over the first half of the examples only."""
    k = int(model["update_every"])
    T = windows * k
    mm = _mm(matmul)
    cell = make_cell(model, matmul)
    lr, b1, b2 = (float(model[x]) for x in ("lr", "b1", "b2"))
    adam_eps = float(model["adam_eps"])

    def history(delta, pstack, xs, ys, flips, w, a0):
        """Sum of window w's per-step losses (each / k), stepping the whole
        history from a0 with the parameters of each step's window plus
        delta."""
        B = xs.shape[1]
        if drop_half_batch:
            keep = jnp.arange(B) < B // 2
        else:
            keep = jnp.ones((B,), bool)

        def body(a, inp):
            t, x, y, flip = inp
            p = jax.tree.map(lambda s, d: s[t // k] + d, pstack, delta)
            a, v = cell(p, a, x, flip)
            logits = mm(a, p["out.W"]) + p["out.b"]
            lp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(lp, y[:, None], axis=1)[:, 0]
            step_loss = jnp.sum(nll * keep) / jnp.sum(keep) / k
            return a, (jnp.where(t // k == w, step_loss, 0.0), v)

        a, (losses, vs) = jax.lax.scan(
            body, a0, (jnp.arange(T), xs, ys, flips))
        return jnp.sum(losses), (vs, a)

    grad_fn = jax.value_and_grad(history, has_aux=True)
    grad_fn_start = jax.value_and_grad(history, argnums=(0, 6),
                                       has_aux=True)

    def run(shared, stream):
        params0, masks = shared["params0"], shared["masks"]
        xs, ys = stream["xs"], stream["ys"]
        start = stream.get("start")
        flips = stream.get("alt")
        n = params0["u.R"].shape[-1]
        if flips is None:
            flips = jnp.zeros(xs.shape[:2] + (n,), bool)
        mask = {name: masks.get(name, jnp.ones_like(v))
                for name, v in params0.items()}
        zeros = jax.tree.map(jnp.zeros_like, params0)
        if start is None:
            p, m, s = params0, zeros, zeros
            a0 = jnp.zeros((xs.shape[1], n), xs.dtype)
        else:
            p, m, s = start["params"], start["m"], start["v"]
            a0 = start["state"]
        pstack = jax.tree.map(lambda v: jnp.stack([v] * windows), p)
        losses, grad1, vs = [], None, None
        for w in range(windows):
            pstack = jax.tree.map(lambda st, v: st.at[w].set(v), pstack, p)
            if start is None:
                # from the zero state: the arithmetic of the reference before
                # starts existed, digit for digit
                (loss, (vs, a)), g = grad_fn(zeros, pstack, xs, ys, flips,
                                             w, a0)
                c1 = 1.0 - b1 ** (w + 1)
                c2 = 1.0 - b2 ** (w + 1)
            else:
                (loss, (vs, a)), (g, lam) = grad_fn_start(
                    zeros, pstack, xs, ys, flips, w, a0)
                # the influence of the history before the start
                g = {name: g_ + (jnp.tensordot(lam, start["influence"][name],
                                               axes=([0, 1], [0, 1]))
                                 if name in start["influence"] else 0.0)
                     for name, g_ in g.items()}
                c1 = 1.0 - b1 ** (start["count"] + w + 1)
                c2 = 1.0 - b2 ** (start["count"] + w + 1)
            g = jax.tree.map(jnp.multiply, g, mask)
            if grad1 is None:
                grad1 = g
            losses.append(loss)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            s = jax.tree.map(lambda s_, g_: b2 * s_ + (1 - b2) * g_ * g_,
                             s, g)
            p = jax.tree.map(
                lambda p_, m_, s_, k_: (p_ - lr * (m_ / c1)
                                        / (jnp.sqrt(s_ / c2) + adam_eps))
                * k_, p, m, s, mask)
        return {"loss": jnp.stack(losses), "grad1": grad1, "params": p,
                "m": m, "v": s, "state": a, "v_pre": vs}

    return run


def alternatives(out: dict) -> list:
    """The `alt` inputs of the near-tie reruns of one stream, from its
    reference run `out` (host arrays): one event flipped each, at most
    MAX_TIES of them."""
    alts = []
    for t in np.argwhere(np.abs(out["v_pre"]) < TIE_MARGIN)[:MAX_TIES]:
        f = np.zeros(out["v_pre"].shape, bool)
        f[tuple(t)] = True
        alts.append(f)
    return alts
