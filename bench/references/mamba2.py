"""Plain reference of a granitemoehybrid period (granite-4.0-h-micro's
layers 30-39) whose top Mamba-2 mixer learns online by AdamW every k steps.

Written from the published architecture (config.json of the model, the
Mamba-2 step of its mixers, GQA attention without position embedding,
SwiGLU MLPs, RMSNorm, residual, embedding and logit multipliers) in plain
`jax.numpy`; it imports nothing of the program under test.

The frozen trunk below the top mixer does not depend on what learns, so
it runs once per stream, layer by layer over the whole sequence: the
position-wise projections as one matmul over every step, the conv, state
and KV-cache recurrences as a scan.  The top mixer then runs step by step
with the parameters of each step's window plus one perturbation delta
shared by every step since the stream began, and the tail above it (its
layer's MLP, the final norm, the tied head, next-token cross-entropy) on
the steps of the window whose loss is taken.  A window's gradient is
reverse mode of its loss with respect to delta: the influence of the past
rides in the conv buffers and the states, with no trace.  A B row and a B
conv channel feed every head's state, but delta reaches only the local
heads' use of them (`local_heads`): the chip's share of the shared rows.

A run may start from a saved state (`start`): the learned parameters,
AdamW's m, v and update count, the program's `state` (every mixer's SSM
state and raw conv buffer, the attention layer's KV cache, positions, the
top mixer's last d_conv - 1 inputs) and the key of the saved traces
(`keep_traces`).  The history before the start then enters twice: the
conv buffer's raw entries move with delta through the saved inputs, and
the top mixer's state through its traces T = dh_0/dtheta.  The second is
lam^T T with lam = dL/dh_0 from the same reverse pass, contracted on the
host in float64 one head at a time, so the host never holds more than one
head's block of the traces in float64.

`matmul`: "highest" (the input dtype, exact passes) or "bf16x3" (each
operand split into a bf16 high and low part, three products summed in f32:
XLA:TPU's `high` precision written out).  `drop_half_batch` plants a fault:
every loss is the mean over the first half of the rows.

Leaves are named by their path joined with "." (`out.z`, `frozen.embed`,
`frozen.layers.3.mlp.wg`); the learned ones carry no `frozen.` prefix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INPUTS = ("tokens", "resets", "targets")
FROZEN = "frozen."
SAVED: dict = {}     # key -> {trace leaf: host float32 [B, Hl, P, N, ...]}

# trace leaf -> (the axes of [B, Hl, P, N, ...] the gradient keeps, whether
# its rows are (h, p) pairs)
TRACE_LEAVES = {"x": ((1, 2), True), "B": ((3,), False),
                "dt": ((1,), False), "conv_x": ((1, 2), True),
                "conv_x_b": ((1, 2), True), "conv_B": ((3,), False),
                "conv_B_b": ((3,), False), "A_log": ((1,), False),
                "dt_bias": ((1,), False)}


def keep_traces(fetch) -> int:
    """Hold a saved state's traces on the host, `fetch()`, in place of any
    held before (about 10 GB at the cell's size): those are dropped before
    `fetch` runs, so the host never holds two.  A start refers to them by
    the key returned."""
    key = max(SAVED, default=0) + 1
    SAVED.clear()
    SAVED[key] = fetch()
    return key


def _contract_host(key, lam):
    """sum lam[b,h,p,n] T[b,h,p,n,...] per trace leaf, over the axes its
    leaf does not keep, in float64, one head of T at a time.  Each result
    as a float32 pair (high, low) whose sum keeps float64's digits: the
    callback runs on a thread without the caller's 64-bit mode."""
    tr = SAVED[int(np.asarray(key).reshape(-1)[0])]
    lam = np.asarray(lam, np.float64).reshape(np.shape(lam)[-4:])
    Hl = lam.shape[1]
    out = {}
    for name, (keep, rows) in TRACE_LEAVES.items():
        parts = []
        for h in range(Hl):
            t = np.asarray(tr[name][:, h], np.float64)      # [B, P, N, ...]
            lt = lam[:, h].reshape(lam[:, h].shape + (1,) * (t.ndim - 3))
            prod = (lt * t).sum(axis=0)                      # [P, N, ...]
            if 2 in keep:                                    # (h, p) rows
                parts.append(prod.sum(axis=1))
            elif 3 in keep:                                  # state column
                parts.append(prod.sum(axis=0))
            else:                                            # the head
                parts.append(prod.sum(axis=(0, 1))[None])
        if 3 in keep:
            g = np.sum(parts, axis=0)
        else:
            g = np.concatenate(parts) if rows or 1 in keep else parts
        g = np.asarray(g)
        hi = g.astype(np.float32)
        out[name] = (hi, (g - hi).astype(np.float32))
    return out


def _ops(matmul: str):
    """(mm, ein): matmul and einsum at the requested arithmetic."""
    if matmul == "highest":
        hi = jax.lax.Precision.HIGHEST
        return (lambda a, b: jnp.matmul(a, b, precision=hi),
                lambda s, a, b: jnp.einsum(s, a, b, precision=hi))
    if matmul == "bf16x3":
        f32, bf = jnp.float32, jnp.bfloat16

        def split(a):
            a = a.astype(f32)
            hi = a.astype(bf)
            return hi, (a - hi.astype(f32)).astype(bf)

        def three(op, a, b):
            (ah, al), (bh, bl) = split(a), split(b)
            return op(ah, bh) + op(ah, bl) + op(al, bh)

        return (lambda a, b: three(functools.partial(
                    jnp.matmul, preferred_element_type=f32), a, b),
                lambda s, a, b: three(functools.partial(
                    jnp.einsum, s, preferred_element_type=f32), a, b))
    raise ValueError(f"unknown matmul mode {matmul!r}")


def _unflat(flat: dict) -> dict:
    """{"a.0.b": x} -> {"a": [{"b": x}]} (integer keys become lists)."""
    root: dict = {}
    for name, v in flat.items():
        parts = name.split(".")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def fix(node):
        if not isinstance(node, dict):
            return node
        node = {k: fix(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node):
            return [node[str(i)] for i in range(len(node))]
        return node
    return fix(root)


def make_reference(model: dict, windows: int, matmul: str = "highest",
                   drop_half_batch: bool = False):
    """Build `run(shared, stream)` for one stream.

    model: the configuration (published keys, `layers_held`, `local_heads`,
    `update_every`, `lr`, `b1`, `b2`, `adam_eps`).  shared: {"params0",
    "masks"} (leaves by name; masks unused).  stream: "tokens", "resets",
    "targets" [windows*k, B] int32 and optionally "start" (module
    docstring).  Returns `loss` [windows], `grad1` (the first window's
    gradient), `params`, `m`, `v` after the last update."""
    mm, ein = _ops(matmul)
    lo, hi_ = model["layers_held"]
    types = tuple(model["layer_types"][lo:hi_])
    D = int(model["hidden_size"])
    H, P = int(model["mamba_n_heads"]), int(model["mamba_d_head"])
    N, Kc = int(model["mamba_d_state"]), int(model["mamba_d_conv"])
    Hq, Hk = (int(model["num_attention_heads"]),
              int(model["num_key_value_heads"]))
    dh = D // Hq
    di = H * P
    h0, h1 = model["local_heads"]
    eps = float(model["rms_norm_eps"])
    rm = float(model["residual_multiplier"])
    k = int(model["update_every"])
    lr, b1, b2 = (float(model[x]) for x in ("lr", "b1", "b2"))
    adam_eps = float(model["adam_eps"])

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(x.dtype)

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def cast(w, like):
        return w.astype(like.dtype)

    def mlp(layer, r):
        h = rms(r, layer["ln2"])
        m = layer["mlp"]
        a = silu(mm(h, cast(m["wg"], h))) * mm(h, cast(m["wi"], h))
        return r + rm * mm(a, cast(m["wo"], h))

    def zero_rows(keep, a):
        return jnp.where(keep.reshape(keep.shape + (1,) * (a.ndim - 1)), a,
                         jnp.zeros((), a.dtype))

    # -- the frozen trunk, once per stream ------------------------------------

    def mixer_seq(m, buf, h, u, keep):
        """A frozen Mamba-2 mixer over the sequence u [T, B, D]."""
        proj = mm(u, cast(m["in"], u).T)
        z, raw, dtr = (proj[..., :di], proj[..., di:2 * di + 2 * N],
                       proj[..., 2 * di + 2 * N:])
        cw, cb = cast(m["conv_w"], u), cast(m["conv_b"], u)
        dtb = cast(m["dt_bias"], u)
        A = -jnp.exp(cast(m["A_log"], u))
        Dk = cast(m["D"], u)

        def body(c, inp):
            buf, h = c
            raw_t, dtr_t, keep_t = inp
            buf, h = zero_rows(keep_t, buf), zero_rows(keep_t, h)
            win = jnp.concatenate([buf, raw_t[:, None]], 1)
            xa = silu(jnp.sum(win * cw.T[None], 1) + cb)
            x = xa[:, :di].reshape(-1, H, P)
            Bv, Cv = xa[:, di:di + N], xa[:, di + N:]
            dt = jax.nn.softplus(dtr_t + dtb)
            a = jnp.exp(dt * A)
            h = a[:, :, None, None] * h + (dt[:, :, None] * x)[..., None] \
                * Bv[:, None, None, :]
            y = ein("bhpn,bn->bhp", h, Cv) + Dk[:, None] * x
            return (win[:, 1:], h), y.reshape(y.shape[0], di)

        (buf, h), ys = jax.lax.scan(body, (buf, h), (raw, dtr, keep))
        g = rms(ys * silu(z), m["norm"])
        return mm(g, cast(m["out"], u).T), buf, h

    def attention_seq(w, kv, u, keep, pos):
        """The frozen GQA attention over the sequence, with its KV cache;
        softmax scale `attention_multiplier`, no position embedding."""
        Tn, Bn, _ = u.shape
        q = mm(u, cast(w["wq"], u)).reshape(Tn, Bn, Hk, Hq // Hk, dh)
        kk = mm(u, cast(w["wk"], u)).reshape(Tn, Bn, Hk, dh)
        vv = mm(u, cast(w["wv"], u)).reshape(Tn, Bn, Hk, dh)
        rows = jnp.arange(Bn)
        slots = jnp.arange(kv["k"].shape[1])

        def body(c, inp):
            K, V = c
            q_t, k_t, v_t, keep_t, pos_t = inp
            K, V = zero_rows(keep_t, K), zero_rows(keep_t, V)
            K = K.at[rows, pos_t].set(k_t)
            V = V.at[rows, pos_t].set(v_t)
            s = ein("bkgd,bskd->bkgs", q_t, K) \
                * float(model["attention_multiplier"])
            valid = slots[None, :] <= pos_t[:, None]
            s = jnp.where(valid[:, None, None], s, -jnp.inf)
            o = ein("bkgs,bskd->bkgd", jax.nn.softmax(s, -1), V)
            return (K, V), o.reshape(Bn, D)

        (K, V), o = jax.lax.scan(body, (kv["k"], kv["v"]),
                                 (q, kk, vv, keep, pos))
        return mm(o, cast(w["wo"], u)), {"k": K, "v": V}

    def trunk(frozen, st, tokens, keep, dtype):
        """-> (r, u) [T, B, D]: the residual into the top layer and its
        normed input; the trunk's state after the sequence is not needed."""
        def pos_body(p, keep_t):
            p = jnp.where(keep_t, p, 0)
            return p + 1, p
        _, pos = jax.lax.scan(pos_body, st["pos"], keep)
        r = frozen["embed"][tokens].astype(dtype) \
            * float(model["embedding_multiplier"])
        im = ia = 0
        for kind, layer in zip(types[:-1], frozen["layers"][:-1]):
            u = rms(r, layer["ln1"])
            if kind == "mamba":
                out, _, _ = mixer_seq(layer["mixer"], st["conv"][im],
                                      st["ssm"][im], u, keep)
                im += 1
            else:
                out, _ = attention_seq(layer["attn"], st["kv"][ia], u, keep,
                                       pos)
                ia += 1
            r = mlp(layer, r + rm * out)
        return r, rms(r, frozen["layers"][-1]["ln1"])

    # -- the learned top mixer and the tail ----------------------------------

    def around(loc, other, unit):
        i = h0 * unit
        return jnp.concatenate([cast(other[:i], loc), loc,
                                cast(other[i:], loc)])

    def top_seq(pstack, delta, rest, u, keep, buf0, h0_, uh0):
        """The top mixer over the sequence: step t uses pstack[t // k] +
        delta.  buf0 [B, K-1, C] the raw conv buffer and uh0 [B, K-1, D]
        the inputs that made it, h0_ [B, H, P, N] the state at the start."""
        dW = {"x": delta["x"], "B": delta["B"], "C": delta["out"]["C"]}
        # the raw entries made before the start move with delta too
        bx = buf0[..., :di] + jnp.concatenate(
            [jnp.zeros(uh0.shape[:2] + (h0 * P,), uh0.dtype),
             ein("bkd,cd->bkc", uh0, dW["x"]),
             jnp.zeros(uh0.shape[:2] + (di - h1 * P,), uh0.dtype)], -1)
        bBl = buf0[..., di:di + N] + ein("bkd,cd->bkc", uh0, dW["B"])
        bBo = buf0[..., di:di + N]
        bC = buf0[..., di + N:] + ein("bkd,cd->bkc", uh0, dW["C"])

        def conv(buf, raw, w, b):
            win = jnp.concatenate([buf, raw[:, None]], 1)
            return jnp.sum(win * w.T[None], 1) + b, win[:, 1:]

        def body(c, inp):
            bx, bBl, bBo, bC, h = c
            t, u_t, keep_t = inp
            po = jax.tree.map(lambda s: s[t // k], pstack)
            p = jax.tree.map(jnp.add, po, delta)
            o = p["out"]
            bx, bBl, bBo, bC, h = (zero_rows(keep_t, a)
                                   for a in (bx, bBl, bBo, bC, h))
            xc, bx = conv(bx, mm(u_t, around(p["x"], rest["x"], P).T),
                          around(p["conv_x"], rest["conv_x"], P),
                          around(p["conv_x_b"], rest["conv_x_b"], P))
            bl, bBl = conv(bBl, mm(u_t, p["B"].T), p["conv_B"],
                           p["conv_B_b"])
            bo, bBo = conv(bBo, mm(u_t, po["B"].T), po["conv_B"],
                           po["conv_B_b"])
            cc, bC = conv(bC, mm(u_t, o["C"].T), o["conv_C"], o["conv_C_b"])
            x = silu(xc).reshape(-1, H, P)
            local = (jnp.arange(H) >= h0) & (jnp.arange(H) < h1)
            Bv = jnp.where(local[None, :, None], silu(bl)[:, None],
                           silu(bo)[:, None])
            dt = jax.nn.softplus(
                mm(u_t, around(p["dt"], rest["dt"], 1).T)
                + around(p["dt_bias"], rest["dt_bias"], 1))
            a = jnp.exp(-dt * jnp.exp(around(p["A_log"], rest["A_log"], 1)))
            h = a[:, :, None, None] * h + (dt[:, :, None] * x)[..., None] \
                * Bv[:, :, None, :]
            y = ein("bhpn,bn->bhp", h, silu(cc)) + o["D"][:, None] * x
            z = mm(u_t, o["z"].T)
            g = rms(y.reshape(y.shape[0], di) * silu(z), o["norm"])
            return (bx, bBl, bBo, bC, h), mm(g, o["proj"].T)

        _, outs = jax.lax.scan(body, (bx, bBl, bBo, bC, h0_),
                               (jnp.arange(u.shape[0]), u, keep))
        return outs

    def tail_loss(frozen, r, out, targets):
        """Sum over steps of the row-mean next-token NLL, / k."""
        rr = mlp(frozen["layers"][-1], r + rm * out)
        h = rms(rr, frozen["final_norm"])
        logits = mm(h, cast(frozen["embed"], h).T) \
            / float(model["logits_scaling"])
        lp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
        Bn = nll.shape[-1]
        rows = (jnp.arange(Bn) < Bn // 2) if drop_half_batch \
            else jnp.ones((Bn,), bool)
        return jnp.sum(jnp.sum(nll * rows, -1) / jnp.sum(rows)) / k

    def window_loss(delta, h0_, pstack, w, frozen, rest, r, u, keep,
                    targets, buf0, uh0):
        outs = top_seq(pstack, delta, rest, u[:(w + 1) * k],
                       keep[:(w + 1) * k], buf0, h0_, uh0)
        sl = slice(w * k, (w + 1) * k)
        return tail_loss(frozen, r[sl], outs[sl], targets[sl])

    grad_fn = jax.value_and_grad(window_loss, argnums=(0, 1))

    def run(shared, stream):
        flat = shared["params0"]
        frozen = _unflat({n[len(FROZEN):]: v for n, v in flat.items()
                          if n.startswith(FROZEN)})
        learned_names = sorted(n for n in flat if not n.startswith(FROZEN))
        dtype = flat[learned_names[0]].dtype
        tokens, targets = stream["tokens"], stream["targets"]
        keep = stream["resets"] == 0
        Bn = tokens.shape[1]
        start = stream.get("start")
        zeros_p = {n: jnp.zeros_like(flat[n]) for n in learned_names}
        if start is None:
            p = {n: flat[n] for n in learned_names}
            m, v, count = zeros_p, zeros_p, 0
            n_m = sum(kind == "mamba" for kind in types)
            n_a = len(types) - n_m
            kv = jnp.zeros((Bn, int(model["kv_slots"]), Hk, dh), dtype)
            st = {"ssm": [jnp.zeros((Bn, H, P, N), dtype)] * n_m,
                  "conv": [jnp.zeros((Bn, Kc - 1, di + 2 * N), dtype)] * n_m,
                  "kv": [{"k": kv, "v": kv}] * n_a,
                  "pos": jnp.zeros((Bn,), jnp.int32),
                  "u_hist": jnp.zeros((Bn, Kc - 1, D), dtype)}
        else:
            p, m, v = start["params"], start["m"], start["v"]
            count, st = start["count"], _unflat(start["state"])
        r, u = trunk(frozen, st, tokens, keep, dtype)
        rest = frozen["layers"][-1]["mixer"]
        p, m, v = _unflat(p), _unflat(m), _unflat(v)
        zeros = jax.tree.map(jnp.zeros_like, p)
        pstack = jax.tree.map(lambda a: jnp.stack([a] * windows), p)
        buf0, hs0, uh0 = st["conv"][-1], st["ssm"][-1], st["u_hist"]
        losses, grad1 = [], None
        for w in range(windows):
            pstack = jax.tree.map(lambda s, a: s.at[w].set(a), pstack, p)
            loss, (g, lam0) = grad_fn(zeros, hs0, pstack, w, frozen, rest,
                                      r, u, keep, targets, buf0, uh0)
            if start is not None:
                half = {n: (jax.ShapeDtypeStruct(g[n].shape, jnp.float32),) * 2
                        for n in TRACE_LEAVES}
                past = jax.pure_callback(_contract_host, half,
                                         start["traces"], lam0[:, h0:h1],
                                         vmap_method="sequential")
                g = dict(g, **{n: g[n] + past[n][0].astype(dtype)
                               + past[n][1].astype(dtype)
                               for n in TRACE_LEAVES})
            if grad1 is None:
                grad1 = g
            losses.append(loss)
            c1 = 1.0 - b1 ** (count + w + 1)
            c2 = 1.0 - b2 ** (count + w + 1)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                             v, g)
            p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / c1)
                             / (jnp.sqrt(v_ / c2) + adam_eps), p, m, v)
        return {"loss": jnp.stack(losses), "grad1": _flat(grad1),
                "params": _flat(p), "m": _flat(m), "v": _flat(v)}

    return run


def _flat(tree, prefix="") -> dict:
    out = {}
    for key, v in tree.items():
        name = f"{prefix}{key}"
        if isinstance(v, dict):
            out.update(_flat(v, name + "."))
        else:
            out[name] = v
    return out
