"""Plain jax.numpy reference of a granitemoehybrid period whose top Mamba-2
mixer learns online, with AdamW every k steps (`repro.cells.mamba2`'s
equations, written out again; it imports nothing of the program).

Every step runs the whole period from the embedding to the loss, every
matmul at HIGHEST precision.  The learned leaves carry one perturbation
delta shared by every step since the stream began (each step using its
own window's parameters plus delta), so the gradient of a window is
reverse mode of the window's loss through the whole history: the influence
of the past rides in the conv buffers and the states, with no trace at all.
A B row and a B conv channel feed the states of every head, but delta
reaches only the local heads' use of them: the chip's share of the shared
rows.

Weights are the program's data: `learned` (its learned leaves) and
`frozen` (every other weight, the top mixer's other heads' rows under
`frozen["layers"][-1]["mixer"]`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def f32(x):
    return x.astype(jnp.float32)


def conv(buf, raw, w, b):
    """Causal depthwise conv: buf [B, K-1, c] the last raw inputs, raw [B,
    c] the new one, w [c, K], b [c]."""
    win = jnp.concatenate([buf, raw[:, None]], 1)
    return jnp.einsum("bkc,ck->bc", win, f32(w), precision=HI) + f32(b), \
        win[:, 1:]


def mlp(layer, x, cfg):
    h = rms(x, layer["ln2"], cfg.rms_norm_eps)
    m = layer["mlp"]
    a = silu(mm(h, f32(m["wg"]))) * mm(h, f32(m["wi"]))
    return x + cfg.residual_multiplier * mm(a, f32(m["wo"]))


def frozen_mixer(cfg, m, st, u):
    di, N = cfg.d_inner, cfg.mamba_d_state
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    proj = mm(u, f32(m["in"]).T)
    z, raw, dtr = proj[:, :di], proj[:, di:di + di + 2 * N], \
        proj[:, 2 * di + 2 * N:]
    xc, buf = conv(st["buf"], raw, m["conv_w"], m["conv_b"])
    xa = silu(xc)
    x, Bv, Cv = xa[:, :di].reshape(-1, H, P), xa[:, di:di + N], xa[:, di + N:]
    dt = jax.nn.softplus(dtr + f32(m["dt_bias"]))
    a = jnp.exp(-dt * jnp.exp(f32(m["A_log"])))
    h = a[:, :, None, None] * st["h"] + (dt[:, :, None] * x)[..., None] \
        * Bv[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, Cv, precision=HI) \
        + f32(m["D"])[:, None] * x
    g = rms(y.reshape(y.shape[0], -1) * silu(z), m["norm"], cfg.rms_norm_eps)
    return mm(g, f32(m["out"]).T), {"buf": buf, "h": h}


def attention(cfg, w, st, u, pos):
    B, D = u.shape
    Hq, Hk = cfg.num_attention_heads, cfg.num_key_value_heads
    dh = D // Hq
    q = mm(u, f32(w["wq"])).reshape(B, Hq, dh)
    k = mm(u, f32(w["wk"])).reshape(B, Hk, dh)
    v = mm(u, f32(w["wv"])).reshape(B, Hk, dh)
    rows = jnp.arange(B)
    K = st["k"].at[rows, pos].set(k)
    V = st["v"].at[rows, pos].set(v)
    kq = jnp.repeat(K, Hq // Hk, axis=2)              # [B, S, Hq, dh]
    vq = jnp.repeat(V, Hq // Hk, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q, kq, precision=HI) \
        * cfg.attention_multiplier
    valid = jnp.arange(K.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, -1), vq, precision=HI)
    return mm(o.reshape(B, D), f32(w["wo"])), {"k": K, "v": V}


def zero_state(cfg, B):
    H, P, N, K = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                  cfg.mamba_d_conv)
    di, D = cfg.d_inner, cfg.hidden_size
    kv = (B, cfg.kv_slots, cfg.num_key_value_heads,
          D // cfg.num_attention_heads)
    layers = []
    for kind in cfg.layer_types[:-1]:
        if kind == "mamba":
            layers.append({"buf": jnp.zeros((B, K - 1, di + 2 * N)),
                           "h": jnp.zeros((B, H, P, N))})
        else:
            layers.append({"k": jnp.zeros(kv), "v": jnp.zeros(kv)})
    top = {"x": jnp.zeros((B, K - 1, di)), "Bl": jnp.zeros((B, K - 1, N)),
           "Bo": jnp.zeros((B, K - 1, N)), "C": jnp.zeros((B, K - 1, N)),
           "h": jnp.zeros((B, H, P, N))}
    return {"layers": layers, "top": top, "pos": jnp.zeros((B,), jnp.int32)}


def top_mixer(cfg, L, rest, st, u):
    """The learned mixer with leaves L (parameters plus delta) and the other
    heads' rows `rest`; B rows and conv channels reach the local heads
    through L and the others through `L_other` = L without delta."""
    L, Lo = L
    di, N = cfg.d_inner, cfg.mamba_d_state
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    h0, h1 = cfg.local_heads
    o = L["out"]

    def around(loc, oth, unit):
        i = h0 * unit
        return jnp.concatenate([f32(oth[:i]), loc, f32(oth[i:])])

    Wx = around(L["x"], rest["x"], P)
    Wdt = around(L["dt"], rest["dt"], 1)
    cx = around(L["conv_x"], rest["conv_x"], P)
    cxb = around(L["conv_x_b"], rest["conv_x_b"], P)
    A_log = around(L["A_log"], rest["A_log"], 1)
    dtb = around(L["dt_bias"], rest["dt_bias"], 1)
    z = mm(u, o["z"].T)
    xc, bx = conv(st["x"], mm(u, Wx.T), cx, cxb)
    bl, bbl = conv(st["Bl"], mm(u, L["B"].T), L["conv_B"], L["conv_B_b"])
    bo, bbo = conv(st["Bo"], mm(u, Lo["B"].T), Lo["conv_B"], Lo["conv_B_b"])
    cc, bc = conv(st["C"], mm(u, o["C"].T), o["conv_C"], o["conv_C_b"])
    x = silu(xc).reshape(-1, H, P)
    local = (jnp.arange(H) >= h0) & (jnp.arange(H) < h1)
    Bv = jnp.where(local[None, :, None], silu(bl)[:, None], silu(bo)[:, None])
    dt = jax.nn.softplus(mm(u, Wdt.T) + dtb)
    a = jnp.exp(-dt * jnp.exp(A_log))
    h = a[:, :, None, None] * st["h"] + (dt[:, :, None] * x)[..., None] \
        * Bv[:, :, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, silu(cc), precision=HI) \
        + o["D"][:, None] * x
    g = rms(y.reshape(y.shape[0], -1) * silu(z), o["norm"], cfg.rms_norm_eps)
    return mm(g, o["proj"].T), {"x": bx, "Bl": bbl, "Bo": bbo, "C": bc,
                                 "h": h}


def step(cfg, L, frozen, st, tok, reset, target):
    """One step of the period -> (mean next-token NLL over rows, state)."""
    keep = reset == 0

    def zero(a):
        return jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)

    st = jax.tree.map(zero, st)
    pos = st["pos"]
    r = f32(frozen["embed"])[tok] * cfg.embedding_multiplier
    layers = []
    for kind, lw, ls in zip(cfg.layer_types[:-1], frozen["layers"][:-1],
                            st["layers"]):
        u = rms(r, lw["ln1"], cfg.rms_norm_eps)
        if kind == "mamba":
            out, ls = frozen_mixer(cfg, lw["mixer"], ls, u)
        else:
            out, ls = attention(cfg, lw["attn"], ls, u, pos)
        layers.append(ls)
        r = mlp(lw, r + cfg.residual_multiplier * out, cfg)
    top = frozen["layers"][-1]
    u = rms(r, top["ln1"], cfg.rms_norm_eps)
    out, ts = top_mixer(cfg, L, top["mixer"], st["top"], u)
    r = mlp(top, r + cfg.residual_multiplier * out, cfg)
    logits = mm(rms(r, frozen["final_norm"], cfg.rms_norm_eps),
                f32(frozen["embed"]).T) / cfg.logits_scaling
    lp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(lp, target[:, None], 1)[:, 0]
    return jnp.mean(nll), {"layers": layers, "top": ts, "pos": pos + 1}


def run(cfg, learned, frozen, tokens, resets, targets, k, windows, lr, b1,
        b2, eps=1e-8):
    """Online AdamW over `windows` windows of k steps from the zero state:
    -> (losses [windows], grads [per window], params after each window)."""
    T, B = tokens.shape
    tokens, resets, targets = map(jnp.asarray, (tokens, resets, targets))
    zeros = jax.tree.map(jnp.zeros_like, learned)

    def history(delta, pstack, w):
        def body(st, t):
            p = jax.tree.map(lambda s, d: s[t // k] + d, pstack, delta)
            loss, st = step(cfg, (p, jax.tree.map(lambda s: s[t // k],
                                                  pstack)),
                            frozen, st, tokens[t], resets[t], targets[t])
            return st, jnp.where(t // k == w, loss / k, 0.0)
        _, losses = jax.lax.scan(body, zero_state(cfg, B), jnp.arange(T))
        return jnp.sum(losses)

    grad = jax.jit(jax.value_and_grad(history), static_argnums=2)
    p, m, v = learned, zeros, zeros
    pstack = jax.tree.map(lambda a: jnp.stack([a] * windows), p)
    losses, grads, params = [], [], []
    for w in range(windows):
        pstack = jax.tree.map(lambda s, a: s.at[w].set(a), pstack, p)
        loss, g = grad(zeros, pstack, w)
        c1, c2 = 1 - b1 ** (w + 1), 1 - b2 ** (w + 1)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / c1)
                         / (jnp.sqrt(v_ / c2) + eps), p, m, v)
        losses.append(loss)
        grads.append(g)
        params.append(p)
    return jnp.stack(losses), grads, params
