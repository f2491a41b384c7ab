"""Granite-4.0-H's hybrid period as a zoo cell: its top Mamba-2 mixer learns
by exact RTRL over layers that run forward only (`jac_kind="head_diagonal"`).

granite-4.0-h-micro (`granitemoehybrid`) stacks Mamba-2 mixers with a GQA
attention layer (no position embedding) every tenth layer, each mixer or
attention followed by a SwiGLU MLP, pre-norm, with both residual branches
scaled by `residual_multiplier`.  The cell holds one period of that stack
(`layer_types`, ending in a Mamba layer), the token embedding scaled by
`embedding_multiplier` entering its first layer, and the tied head (logits
over `logits_scaling`).  Every layer but the top mixer is frozen.

One Mamba-2 step (n_groups 1), per head h, head row p, state column n:

    z, xBC, dt_raw = in_proj u_t            (u_t the normed layer input)
    xBC_c = sum_k conv_w[:, k] xBC_{t-3+k} + conv_b;   x, B, C = silu(xBC_c)
    dt = softplus(dt_raw + dt_bias),  A = -exp(A_log),  a = exp(dt A)
    h_t[h,p,n] = a[h] h_{t-1}[h,p,n] + dt[h] x[h,p] B[n]
    y[h,p] = sum_n C[n] h_t[h,p,n] + D[h] x[h,p]
    out = out_proj rmsnorm(y * silu(z))

The state Jacobian is one scalar per head, a[h], and each projection row
feeds one row or column of the state: an x row one (h, p) row, a B row one
column n, a dt row one head.  So every exact trace dh_t/dtheta of the top
mixer is `a[h] * trace + increment`, where the increment is rank-1 over the
projection's input axis j: for an x row `(dt sx B)[h,p,n] * ux[h,p,j]`, for
a B row `(dt x)[h,p] * (sB uB)[n,j]`, for a dt row `g[h,p,n] * u[j]`
(`trace_increments`).  Here ux, uB are the conv-mixed inputs,
`sum_k conv_w[c, k] u_{t-3+k}`: a projection row reaches xBC_c through the
last d_conv inputs, weighted by the current taps.

The chip's share of a deployment that divides the top mixer's heads over
chips (`local_heads`, [h0, h1)): the readout side learns in full (the z and
C rows of in_proj, C's conv channels, D, the gated norm's weight,
out_proj: `params["out"]`, whose gradients are immediate, by reverse mode
through the frozen tail), and the state side of the local heads only (their
x and dt rows, x conv channels, A_log, dt_bias) plus this chip's share of
the shared B rows and B conv channels: the gradient through the local
heads' states.  The other heads run forward and are not updated here.  The
D skip reaches the loss without the state: its immediate term goes to the
x rows and x conv channels too.

Input per step: `x_t` int32 [B, 2], the token id and a reset flag (1 where
the token starts a document: that row's states, conv buffers, KV cache,
position and traces go to zero first), `y_t` int32 [B] the next token.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssm_trace import ssm_trace_update
from repro.models import attention as MA
from repro.models import layers as ML

Tree = Any
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """One period of a granitemoehybrid stack; names as in the published
    config.json.  `local_heads` = [h0, h1), the top mixer's heads whose
    state side this chip learns and carries traces for."""
    hidden_size: int = 2048
    intermediate_size: int = 8192
    vocab_size: int = 100352
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    local_heads: tuple = (0, 8)
    kv_slots: int = 4096
    init_std: float = 0.02

    def __post_init__(self):
        if self.layer_types[-1] != MAMBA:
            raise ValueError("the period's top layer must be a Mamba layer")
        if self.mamba_n_groups != 1:
            raise ValueError("only mamba_n_groups = 1 is supported")
        h0, h1 = self.local_heads
        if not 0 <= h0 < h1 <= self.mamba_n_heads:
            raise ValueError(f"bad local_heads {self.local_heads}")

    def replace(self, **kw) -> "GraniteHybridConfig":
        return dataclasses.replace(self, **kw)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def n_local(self) -> int:
        return self.local_heads[1] - self.local_heads[0]

    def model_config(self) -> ModelConfig:
        """The seed's model config for the frozen attention and MLPs."""
        return ModelConfig(
            name="granite-period", family="decoder",
            n_layers=len(self.layer_types), d_model=self.hidden_size,
            n_heads=self.num_attention_heads,
            n_kv_heads=self.num_key_value_heads, d_ff=self.intermediate_size,
            vocab_size=self.vocab_size, pos_emb="none", mlp_act="swiglu",
            attn_logits_scale=self.attention_multiplier,
            param_dtype=jnp.bfloat16, compute_dtype=jnp.float32,
            norm_eps=self.rms_norm_eps, tie_embeddings=True)


# -- parameters ---------------------------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_mixer(cfg: GraniteHybridConfig, key, dtype) -> dict:
    """Mamba-2's initialiser: projections normal(init_std), conv as
    PyTorch's Conv1d default U(+-1/sqrt(d_conv)), A_log = log U(1, 16),
    dt_bias the inverse softplus of a log-uniform dt in [1e-3, 1e-1], D = 1,
    the gated norm's weight 1."""
    H, D, K = cfg.mamba_n_heads, cfg.hidden_size, cfg.mamba_d_conv
    ks = jax.random.split(key, 7)
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(ks[4], (H,), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    f32 = jnp.float32
    return {
        "in": _normal(ks[0], (2 * cfg.d_inner + 2 * cfg.mamba_d_state + H,
                              D), cfg.init_std, dtype),
        "conv_w": jax.random.uniform(ks[1], (cfg.conv_dim, K), f32, -bound,
                                     bound).astype(dtype),
        "conv_b": jax.random.uniform(ks[2], (cfg.conv_dim,), f32, -bound,
                                     bound).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), f32, 1.0, 16.0)
                         ).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": jnp.ones((cfg.d_inner,), dtype),
        "out": _normal(ks[5], (D, cfg.d_inner), cfg.init_std, dtype),
    }


def init_layer(cfg: GraniteHybridConfig, kind: str, key, dtype) -> dict:
    D, F = cfg.hidden_size, cfg.intermediate_size
    s = cfg.init_std
    ks = jax.random.split(key, 8)
    layer = {"ln1": jnp.ones((D,), dtype), "ln2": jnp.ones((D,), dtype),
             "mlp": {"wg": _normal(ks[0], (D, F), s, dtype),
                     "wi": _normal(ks[1], (D, F), s, dtype),
                     "wo": _normal(ks[2], (F, D), s, dtype)}}
    if kind == MAMBA:
        layer["mixer"] = init_mixer(cfg, ks[3], dtype)
    else:
        q = cfg.num_attention_heads * (D // cfg.num_attention_heads)
        kv = cfg.num_key_value_heads * (D // cfg.num_attention_heads)
        layer["attn"] = {"wq": _normal(ks[4], (D, q), s, dtype),
                         "wk": _normal(ks[5], (D, kv), s, dtype),
                         "wv": _normal(ks[6], (D, kv), s, dtype),
                         "wo": _normal(ks[7], (q, D), s, dtype)}
    return layer


def _rows(cfg: GraniteHybridConfig) -> dict:
    """Row ranges of in_proj (z, x, B, C, dt) and of the conv channels."""
    di, N, H = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_n_heads
    return {"z": (0, di), "x": (di, 2 * di), "B": (2 * di, 2 * di + N),
            "C": (2 * di + N, 2 * di + 2 * N),
            "dt": (2 * di + 2 * N, 2 * di + 2 * N + H),
            "cx": (0, di), "cB": (di, di + N), "cC": (di + N, di + 2 * N)}


def split_top(cfg: GraniteHybridConfig, m: dict) -> tuple[dict, dict]:
    """A full mixer -> (learned, rest): what this chip learns (module
    docstring) and the other heads' state-side rows, which stay frozen."""
    r = _rows(cfg)
    P = cfg.mamba_d_head
    h0, h1 = cfg.local_heads

    def cut(a, lo, hi, unit=1):
        """a[lo:hi] split into (the local slice, the rest around it)."""
        i0, i1 = lo + h0 * unit, lo + h1 * unit
        return a[i0:i1], jnp.concatenate([a[lo:i0], a[i1:hi]])

    W, cw, cb = m["in"], m["conv_w"], m["conv_b"]
    x, x_rest = cut(W, *r["x"], P)
    dt, dt_rest = cut(W, *r["dt"])
    cx, cx_rest = cut(cw, *r["cx"], P)
    cxb, cxb_rest = cut(cb, *r["cx"], P)
    al, al_rest = cut(m["A_log"], 0, cfg.mamba_n_heads)
    db, db_rest = cut(m["dt_bias"], 0, cfg.mamba_n_heads)
    learned = {
        "x": x, "dt": dt, "conv_x": cx, "conv_x_b": cxb, "A_log": al,
        "dt_bias": db, "B": W[slice(*r["B"])], "conv_B": cw[slice(*r["cB"])],
        "conv_B_b": cb[slice(*r["cB"])],
        "out": {"z": W[slice(*r["z"])], "C": W[slice(*r["C"])],
                "conv_C": cw[slice(*r["cC"])],
                "conv_C_b": cb[slice(*r["cC"])], "D": m["D"],
                "norm": m["norm"], "proj": m["out"]}}
    rest = {"x": x_rest, "dt": dt_rest, "conv_x": cx_rest,
            "conv_x_b": cxb_rest, "A_log": al_rest, "dt_bias": db_rest}
    return learned, rest


def join_top(cfg: GraniteHybridConfig, learned: dict, rest: dict) -> dict:
    """The inverse of `split_top`, in float32: the full top mixer."""
    P = cfg.mamba_d_head
    h0 = cfg.local_heads[0]
    f32 = jnp.float32

    def put(loc, other, unit=1):
        i = h0 * unit
        return jnp.concatenate([other[:i].astype(f32), loc.astype(f32),
                                other[i:].astype(f32)])

    o = learned["out"]
    return {
        "in": jnp.concatenate([o["z"], put(learned["x"], rest["x"], P),
                               learned["B"], o["C"],
                               put(learned["dt"], rest["dt"])]),
        "conv_w": jnp.concatenate([put(learned["conv_x"], rest["conv_x"], P),
                                   learned["conv_B"], o["conv_C"]]),
        "conv_b": jnp.concatenate([put(learned["conv_x_b"],
                                       rest["conv_x_b"], P),
                                   learned["conv_B_b"], o["conv_C_b"]]),
        "A_log": put(learned["A_log"], rest["A_log"]),
        "dt_bias": put(learned["dt_bias"], rest["dt_bias"]),
        "D": o["D"], "norm": o["norm"], "out": o["proj"]}


def init_params(cfg: GraniteHybridConfig, key,
                dtype=jnp.bfloat16) -> tuple[dict, dict]:
    """(learned, frozen) from one key: every weight drawn in `dtype` (bf16
    as published), the learned share of the top mixer then held in float32.
    The weights do not depend on `local_heads`, which only decides how the
    top mixer is split."""
    k_emb, k_layers = jax.random.split(key)
    kinds = cfg.layer_types
    keys = jax.random.split(k_layers, len(kinds))
    layers = [init_layer(cfg, kind, k, dtype)
              for kind, k in zip(kinds[:-1], keys[:-1])]
    top = init_layer(cfg, MAMBA, keys[-1], dtype)
    learned, rest = split_top(cfg, top["mixer"])
    learned = jax.tree.map(lambda a: a.astype(jnp.float32), learned)
    top["mixer"] = rest
    frozen = {"embed": _normal(k_emb, (cfg.vocab_size, cfg.hidden_size),
                               cfg.init_std, dtype),
              "final_norm": jnp.ones((cfg.hidden_size,), dtype),
              "layers": tuple(layers) + (top,)}
    return learned, frozen


# -- state ---------------------------------------------------------------------

def init_state(cfg: GraniteHybridConfig, batch: int) -> dict:
    """Per layer: the SSM state [B, H, P, N] and the last d_conv - 1 raw
    xBC [B, K-1, conv_dim] of each mixer, the KV cache of each attention
    layer; the rows' positions; the top mixer's last d_conv - 1 inputs."""
    f32 = jnp.float32
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    K, D = cfg.mamba_d_conv, cfg.hidden_size
    n_m = sum(k == MAMBA for k in cfg.layer_types)
    n_a = len(cfg.layer_types) - n_m
    kv = (batch, cfg.kv_slots, cfg.num_key_value_heads,
          D // cfg.num_attention_heads)
    return {"ssm": tuple(jnp.zeros((batch, H, P, N), f32)
                         for _ in range(n_m)),
            "conv": tuple(jnp.zeros((batch, K - 1, cfg.conv_dim), f32)
                          for _ in range(n_m)),
            "kv": tuple({"k": jnp.zeros(kv, f32), "v": jnp.zeros(kv, f32)}
                        for _ in range(n_a)),
            "pos": jnp.zeros((batch,), jnp.int32),
            "u_hist": jnp.zeros((batch, K - 1, D), f32)}


def init_traces(cfg: GraniteHybridConfig, batch: int) -> dict:
    """dh/dtheta of the local heads' states [B, Hl, P, N] for each learned
    state-side leaf, the leaf's own axes trailing where it has any."""
    Hl, P, N = cfg.n_local, cfg.mamba_d_head, cfg.mamba_d_state
    D, K = cfg.hidden_size, cfg.mamba_d_conv
    s = (batch, Hl, P, N)
    z = jnp.zeros
    return {"x": z(s + (D,)), "B": z(s + (D,)), "dt": z(s + (D,)),
            "conv_x": z(s + (K,)), "conv_x_b": z(s),
            "conv_B": z(s + (K,)), "conv_B_b": z(s),
            "A_log": z(s), "dt_bias": z(s)}


def reset_rows(state: dict, keep: jax.Array) -> dict:
    """Zero the rows whose `keep` is False: a document starts there."""
    def zero(a):
        k = keep.reshape(keep.shape + (1,) * (a.ndim - 1))
        return jnp.where(k, a, jnp.zeros((), a.dtype))
    return jax.tree.map(zero, state)


# -- forward -------------------------------------------------------------------

def silu_grad(v):
    s = jax.nn.sigmoid(v)
    return s * (1.0 + v * (1.0 - s))


def mixer_step(cfg: GraniteHybridConfig, m: dict, conv, ssm, u):
    """One Mamba-2 step of a mixer `m` (full weights, any dtype, computed
    in float32).  conv [B, K-1, C] the last raw xBC, ssm [B, H, P, N], u
    [B, D] the normed input.  -> (out [B, D], conv', ssm', aux)."""
    f32 = jnp.float32
    di, N = cfg.d_inner, cfg.mamba_d_state
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    proj = u @ m["in"].astype(f32).T
    z, xbc, dtr = (proj[:, :di], proj[:, di:di + cfg.conv_dim],
                   proj[:, di + cfg.conv_dim:])
    win = jnp.concatenate([conv, xbc[:, None]], axis=1)         # [B, K, C]
    xc = (jnp.sum(win * m["conv_w"].astype(f32).T[None], axis=1)
          + m["conv_b"].astype(f32))
    xa = jax.nn.silu(xc)
    x = xa[:, :di].reshape(-1, H, P)
    Bv, Cv = xa[:, di:di + N], xa[:, di + N:]
    dtr = dtr + m["dt_bias"].astype(f32)
    dt = jax.nn.softplus(dtr)
    A = -jnp.exp(m["A_log"].astype(f32))
    a = jnp.exp(dt * A)
    h = (a[:, :, None, None] * ssm
         + (dt[:, :, None] * x)[..., None] * Bv[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", h, Cv) + m["D"].astype(f32)[:, None] * x
    out = gated_out(cfg, m["norm"], m["out"], y, z)
    aux = {"win": win, "xc": xc, "dtr": dtr, "dt": dt, "A": A, "a": a,
           "x": x, "Bv": Bv}
    return out, win[:, 1:], h, aux


def gated_out(cfg: GraniteHybridConfig, norm_w, proj_w, y, z):
    """out_proj of the gated RMSNorm over d_inner: norm(y * silu(z))."""
    f32 = jnp.float32
    g = y.reshape(y.shape[0], -1) * jax.nn.silu(z)
    return ML.rmsnorm(g, norm_w, cfg.rms_norm_eps) @ proj_w.astype(f32).T


def mlp_residual(cfg: GraniteHybridConfig, layer: dict, r):
    """The post-mixer half of a layer: r + rm * MLP(norm(r))."""
    mc = cfg.model_config()
    h = ML.rmsnorm(r, layer["ln2"], cfg.rms_norm_eps)
    return r + cfg.residual_multiplier * ML.mlp(mc, layer["mlp"], h)


def trunk(cfg: GraniteHybridConfig, frozen: dict, state: dict, tokens):
    """The forward-only part below the top mixer: the scaled embedding and
    every layer but the top one, then the top layer's input norm.
    -> (r [B, D] the residual into the top layer, u [B, D] the top mixer's
    input, state with every layer below updated and positions advanced)."""
    mc = cfg.model_config()
    rm, eps = cfg.residual_multiplier, cfg.rms_norm_eps
    r = ML.embed_tokens(mc, {"tok": frozen["embed"]}, tokens)
    r = r * cfg.embedding_multiplier
    ssm, conv, kv = list(state["ssm"]), list(state["conv"]), list(state["kv"])
    pos = state["pos"]
    im = ia = 0
    for kind, layer in zip(cfg.layer_types[:-1], frozen["layers"][:-1]):
        u = ML.rmsnorm(r, layer["ln1"], eps)
        if kind == MAMBA:
            out, conv[im], ssm[im], _ = mixer_step(cfg, layer["mixer"],
                                                   conv[im], ssm[im], u)
            im += 1
        else:
            o, kv[ia] = MA.self_attention_decode(mc, layer["attn"],
                                                 u[:, None], kv[ia], pos)
            out = o[:, 0]
            ia += 1
        r = mlp_residual(cfg, layer, r + rm * out)
    u = ML.rmsnorm(r, frozen["layers"][-1]["ln1"], eps)
    state = dict(state, ssm=tuple(ssm), conv=tuple(conv), kv=tuple(kv),
                 pos=pos + 1)
    return r, u, state


def head_loss(cfg: GraniteHybridConfig, frozen: dict, r, y_t, tt):
    """The tail above the top mixer: its layer's MLP, the final norm, the
    tied head over `logits_scaling`, next-token cross-entropy (mean over
    rows) / tt.  -> (loss, logits)."""
    mc = cfg.model_config()
    r = mlp_residual(cfg, frozen["layers"][-1], r)
    h = ML.rmsnorm(r, frozen["final_norm"], cfg.rms_norm_eps)
    logits = ML.lm_logits(mc, {"tok": frozen["embed"]}, h)
    logits = logits / cfg.logits_scaling
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, y_t[:, None], axis=1)[:, 0]
    return jnp.mean(nll) / tt, logits


# -- exact RTRL terms of the top mixer ------------------------------------------

def _mixed(w, U):
    """sum_k w[c, k] U[b, k, j] -> [B, C, D]: a projection row's derivative
    of its conv output (the conv-mixed input)."""
    return jnp.einsum("ck,bkj->bcj", w, U)


def exact_terms(cfg: GraniteHybridConfig, params: dict, frozen: dict,
                state: dict, x_t, y_t, tt):
    """One step of the period with the top mixer's exact-RTRL terms.

    -> (state', decay [B, Hl], increments, lam [B, Hl, P, N], g_rec,
    g_out, loss, None, stats): the local heads' decay a (0 on a reset
    row), the trace increments (`trace_increments`), lam = dL_t/dh_t of
    the local heads, and the immediate gradients of the learned leaves:
    state-side (`g_rec`, through D x) and readout side (`g_out`); the
    logits stay inside the tail."""
    f32 = jnp.float32
    di, N, P = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_head
    h0, h1 = cfg.local_heads
    keep = x_t[:, 1] == 0
    state = reset_rows(state, keep)
    with jax.named_scope("frozen_blocks"):
        r, u, state = trunk(cfg, frozen, state, x_t[:, 0])
    top = frozen["layers"][-1]
    with jax.named_scope("ssm_readout"):
        m = join_top(cfg, params, top["mixer"])
        _, conv, h_new, aux = mixer_step(cfg, m, state["conv"][-1],
                                         state["ssm"][-1], u)
        U = jnp.concatenate([state["u_hist"], u[:, None]], axis=1)  # [B,K,D]
        win = aux["win"]
        xs, xe = di + h0 * P, di + h1 * P
        sg = jax.lax.stop_gradient

        def readout(lp, h):
            """The loss from the new state h (held) and the learned leaves:
            y = C h + D x, the gated norm, out_proj, the residual, and the
            frozen tail.  C and the local x are rebuilt from the raw conv
            window (the trajectory's values) with a zero-valued term whose
            derivative in a projection row is its conv-mixed input, so
            reverse mode gives the exact immediate gradients."""
            o = lp["out"]
            cw = jnp.concatenate([lp["conv_x"], o["conv_C"]])
            cb = jnp.concatenate([lp["conv_x_b"], o["conv_C_b"]])
            W = jnp.concatenate([lp["x"], o["C"]])
            w_ch = jnp.concatenate([win[:, :, xs - di:xe - di],
                                    win[:, :, di + N:]], axis=-1)  # [B,K,c]
            mix = _mixed(sg(cw), U)                               # [B,c,D]
            pre = (jnp.sum(w_ch * cw.T[None], axis=1) + cb
                   + jnp.sum((W - sg(W))[None] * mix, axis=-1))
            act = jax.nn.silu(pre)
            x_loc, Cv = act[:, :xe - xs], act[:, xe - xs:]
            x = aux["x"].reshape(-1, di)
            x = jnp.concatenate([sg(x[:, :xs - di]), x_loc,
                                 sg(x[:, xe - di:])], axis=1)
            x = x.reshape(h.shape[:3])
            y = jnp.einsum("bhpn,bn->bhp", h, Cv) + o["D"][:, None] * x
            z = u @ o["z"].T
            out = gated_out(cfg, o["norm"], o["proj"], y, z)
            rr = r + cfg.residual_multiplier * out
            with jax.named_scope("frozen_blocks"):
                loss, _ = head_loss(cfg, frozen, rr, y_t, tt)
            return loss

        lt, vjp = jax.vjp(readout, params, h_new)
        g_lp, g_h = vjp(jnp.ones((), f32))
    with jax.named_scope("ssm_trace_update"):
        h_prev = state["ssm"][-1][:, h0:h1]
        inc = trace_increments(cfg, params, aux, U, h_prev)
    lam = g_h[:, h0:h1]
    decay = aux["a"][:, h0:h1] * keep[:, None].astype(f32)
    state = dict(state, conv=state["conv"][:-1] + (conv,),
                 ssm=state["ssm"][:-1] + (h_new,), u_hist=U[:, 1:])
    g_rec = {k: v for k, v in g_lp.items() if k != "out"}
    stats = {"resets": jnp.sum(1 - keep.astype(f32)),
             "tokens": jnp.asarray(x_t.shape[0], f32)}
    return state, decay, inc, lam, g_rec, g_lp["out"], lt, None, stats


def trace_increments(cfg: GraniteHybridConfig, params: dict, aux: dict, U,
                     h_prev) -> dict:
    """dh_t/dtheta with h_{t-1} held, for the local heads: the big leaves
    as their rank-1 factors, the small leaves whole (see the module
    docstring).  h_prev [B, Hl, P, N] is the state before the step (zero on
    a reset row)."""
    di, N, P = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_head
    h0, h1 = cfg.local_heads
    Hl = h1 - h0
    xc, win = aux["xc"], aux["win"]
    x = aux["x"][:, h0:h1]                                    # [B,Hl,P]
    sx = silu_grad(xc[:, h0 * P:h1 * P]).reshape(-1, Hl, P)
    Bv = aux["Bv"]                                            # [B,N]
    sB = silu_grad(xc[:, di:di + N])
    dt, a = aux["dt"][:, h0:h1], aux["a"][:, h0:h1]           # [B,Hl]
    A = aux["A"][h0:h1]
    sdt = jax.nn.sigmoid(aux["dtr"][:, h0:h1])
    u = U[:, -1]
    fx = (dt[:, :, None] * sx)[..., None] * Bv[:, None, None, :]  # [B,Hl,P,N]
    fB = dt[:, :, None] * x                                       # [B,Hl,P]
    gdt = sdt[:, :, None, None] * ((A * a)[:, :, None, None] * h_prev
                                   + x[..., None] * Bv[:, None, None, :])
    ux = _mixed(params["conv_x"], U).reshape(-1, Hl, P, U.shape[-1])
    uB = sB[..., None] * _mixed(params["conv_B"], U)              # [B,N,D]
    wx = win[:, :, h0 * P:h1 * P].reshape(-1, win.shape[1], Hl, P)
    wB = win[:, :, di:di + N]                                     # [B,K,N]
    fBn = fB[..., None] * sB[:, None, None, :]                    # [B,Hl,P,N]
    return {
        "x": (fx, ux), "B": (fB, uB), "dt": (gdt, u),
        "conv_x": fx[..., None] * jnp.moveaxis(wx, 1, -1)[:, :, :, None, :],
        "conv_x_b": fx,
        "conv_B": fBn[..., None] * jnp.moveaxis(wB, 1, -1)[:, None, None],
        "conv_B_b": fBn,
        "A_log": (a * dt * A)[:, :, None, None] * h_prev,
        "dt_bias": gdt}


class Mamba2PeriodCell:
    """granitemoehybrid's period behind the cell protocol, with
    `jac_kind="head_diagonal"`: `DiagExactLearner` takes its step terms
    from `exact_terms` and its trace update from `trace_update` (one pass
    over the traces, `kernels/ssm_trace.py`).  `params` are the learned
    leaves (`split_top`); the frozen weights ride in the carry."""

    name = "mamba2"
    jac_kind = "head_diagonal"

    def __init__(self, cfg: GraniteHybridConfig):
        self.cfg = cfg

    def init_params(self, key) -> Tree:
        return init_params(self.cfg, key)[0]

    def rec_params(self, params: Tree) -> Tree:
        return {k: v for k, v in params.items() if k != "out"}

    def init_state(self, batch: int) -> dict:
        return init_state(self.cfg, batch)

    def init_traces(self, batch: int) -> dict:
        return init_traces(self.cfg, batch)

    def exact_terms(self, params, frozen, state, x_t, y_t, tt, masks=None):
        if frozen is None:
            raise ValueError(f"the {self.name!r} cell needs its frozen "
                             "weights: init(..., frozen=...)")
        if masks is not None:
            raise ValueError(f"the {self.name!r} cell takes no masks")
        return exact_terms(self.cfg, params, frozen, state, x_t, y_t, tt)

    def trace_update(self, tr, decay, inc, lam):
        with jax.named_scope("ssm_trace_update"):
            return ssm_trace_update(tr, decay, inc, lam)

    def partials(self, w, state, x_t):
        raise NotImplementedError(
            "the head-diagonal cell hands its step terms to the learner "
            "through exact_terms")

    def step_st(self, w, state, x_t):
        raise NotImplementedError(
            "the period's step needs its frozen weights: exact_terms")

    def readout(self, params: Tree, state: Any) -> jax.Array:
        raise NotImplementedError(
            "the readout is the frozen tail inside exact_terms")

    def activity_mask(self, state: Any) -> jax.Array:
        return jnp.ones(state["pos"].shape, bool)
