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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

GATES = ("u", "r", "z")


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


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def make_reference(model: dict, windows: int, matmul: str = "highest",
                   drop_half_batch: bool = False):
    """Build `run(params0, masks, xs, ys, flips)` for one stream.

    model: the configuration's cell and optimizer entries (n_hidden, gamma,
    eps, update_every, lr, b1, b2, adam_eps).  xs [windows*k, B, n_in],
    ys [windows*k, B] int32, flips [windows*k, B, n] bool (all False except
    where a near-tie is resolved the other way).  Returns a dict: `loss`
    [windows] (each window's loss before its update), `grad1` (the masked
    gradient of the first window), `params` (after `windows` updates) and
    `v` [windows*k, B, n] (the pre-activations along the trajectory).

    drop_half_batch plants a fault for the benchmark's own checks: every
    loss is the mean over the first half of the examples only."""
    k = int(model["update_every"])
    T = windows * k
    mm = _mm(matmul)
    h = _heaviside_st(float(model["gamma"]), float(model["eps"]))
    lr, b1, b2 = (float(model[x]) for x in ("lr", "b1", "b2"))
    adam_eps = float(model["adam_eps"])

    def cell(p, a, x, flip):
        u = jax.nn.sigmoid(mm(x, p["u.W"]) + mm(a, p["u.R"]) + p["u.b"])
        r = jax.nn.sigmoid(mm(x, p["r.W"]) + mm(a, p["r.R"]) + p["r.b"])
        z = jnp.tanh(mm(x, p["z.W"]) + mm(r * a, p["z.R"]) + p["z.b"])
        v = u * z + (1.0 - u) * a - p["theta"]
        return h(v, flip), v

    def history(delta, pstack, xs, ys, flips, w):
        """Sum of window w's per-step losses (each / k), stepping the whole
        history with the parameters of each step's window plus delta."""
        B, n = xs.shape[1], pstack["u.R"].shape[-1]
        a0 = jnp.zeros((B, n), xs.dtype)
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

        _, (losses, vs) = jax.lax.scan(
            body, a0, (jnp.arange(T), xs, ys, flips))
        return jnp.sum(losses), vs

    grad_fn = jax.value_and_grad(history, has_aux=True)

    def run(params0, masks, xs, ys, flips):
        mask = {name: masks.get(name, jnp.ones_like(v))
                for name, v in params0.items()}
        zeros = jax.tree.map(jnp.zeros_like, params0)
        pstack = jax.tree.map(lambda v: jnp.stack([v] * windows), params0)
        p, m, s = params0, zeros, zeros
        losses, grad1, vs = [], None, None
        for w in range(windows):
            pstack = jax.tree.map(lambda st, v: st.at[w].set(v), pstack, p)
            (loss, vs), g = grad_fn(zeros, pstack, xs, ys, flips, w)
            g = jax.tree.map(jnp.multiply, g, mask)
            if grad1 is None:
                grad1 = g
            losses.append(loss)
            c1 = 1.0 - b1 ** (w + 1)
            c2 = 1.0 - b2 ** (w + 1)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            s = jax.tree.map(lambda s_, g_: b2 * s_ + (1 - b2) * g_ * g_,
                             s, g)
            p = jax.tree.map(
                lambda p_, m_, s_, k_: (p_ - lr * (m_ / c1)
                                        / (jnp.sqrt(s_ / c2) + adam_eps))
                * k_, p, m, s, mask)
        return {"loss": jnp.stack(losses), "grad1": grad1, "params": p,
                "v": vs}

    return run
