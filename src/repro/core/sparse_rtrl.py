"""EXACT RTRL with combined activity + parameter sparsity (the paper's core).

Closed-form per-step partials for the threshold cells in `repro.core.cells`
exploit the structure of Eqs. (6)-(10):

  * J_t   = D(H'(v_t)) . J-hat_t          -> beta_t . n rows are exactly zero
  * Mbar_t = D(H'(v_t)) . (per-unit groups) -> same rows zero; one parameter
    group (W[:,k'], R[:,k'], b_k' [, theta_k']) per unit k' (paper's m =
    n + n_in + 1), so M factors as [B, n, n, m] with p = n*m.
  * fixed parameter-sparsity masks zero columns of Mbar/M permanently and
    sparsify J through R (Sec. 5) — invariants asserted in tests.

Two representations of the influence matrix coexist:

  * the per-gate dict ({u,r,z,theta} / {v}: [B, n, n, m]) used by the
    masked-dense reference path — the exactness oracle;
  * the FLAT layout M [B, n, P] (`FlatLayout`): all gates' (q, m) column
    groups concatenated along one lane-padded axis, so ONE kernel invocation
    per step covers every gate.  This is the engine's native form — it is
    what the block-sparse Pallas kernel (repro/kernels/influence.py) and the
    row-compaction path (repro/kernels/compact.py) consume.

`sparse_rtrl_loss_and_grads(..., backend=)` selects the execution strategy:

  backend="dense"    masked-dense per-gate einsums (reference; default)
  backend="pallas"   flat layout + block-sparse Pallas kernel, fed per-step
                     row/col/J block masks derived from hp and the masks
  backend="compact"  flat layout carried row-compact ([B, K, P] + indices);
                     FLOPs ~ beta~(t) beta~(t-1) n^2 p, with gradient
                     extraction c-bar^T M fused into the compact form

With fixed parameter masks the live column set is STATIC, so the pallas and
compact backends additionally carry the parameter axis COLUMN-compact
(col_compact=, default on whenever masks are given): `ColLayout` maps the
Pc ~= w~ P live columns, M-bar is built directly at compact width, and the
carry/contraction shrink to [B, K, Pc] / K K' Pc — the paper's COMBINED
w~ beta~(t) beta~(t-1) n^2 p compute and w~ beta~ n p memory, physically.

All backends produce gradients equal to `repro.core.rtrl` (generic oracle)
and to BPTT — the paper's "without any approximations" claim; `repro.core.
costs` does the paper's own "compute-adjusted" op accounting from the
measured beta/omega.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cells
from repro.core.cells import EGRUConfig

Tree = Any

LANE = 128        # TPU lane width: flat influence buffers are lane-padded


# ---------------------------------------------------------------------------
# Parameter-sparsity masks (fixed at init — paper Sec. 6)
# ---------------------------------------------------------------------------

def mask_gates(kind: str) -> tuple:
    """The gates whose W/R matrices are maskable, in canonical order — the
    order every mask-key convention below folds over."""
    return ("v",) if kind == "rnn" else ("u", "r", "z")


def gate_param_keys(key: jax.Array, gates: tuple) -> dict:
    """THE per-call key split convention for mask draws: gate i (in `gates`
    order) folds the base key with i, then splits once into the (W, R) draw
    keys.  `make_masks` consumes its key through this helper, and rewire
    events (`repro.sparsity.schedule`) reuse it with the per-event key from
    `RewireSchedule.event_key` — every mask draw, at init or at any
    prune-and-regrow event, is fully determined by (base key, gate order),
    with no ad-hoc folding at call sites."""
    out = {}
    for i, g in enumerate(gates):
        kW, kR = jax.random.split(jax.random.fold_in(key, i))
        out[g] = {"W": kW, "R": kR}
    return out


def make_masks(cfg: EGRUConfig, key: jax.Array, sparsity: float,
               block: int = 1, mask_input: bool = True) -> Tree:
    """Random fixed masks with density (1-sparsity).

    block > 1 draws the mask at [block x block] granularity — the
    TPU-friendly variant (DESIGN.md §3); block=1 is the paper's unstructured
    setting.

    `key` is consumed through `gate_param_keys` (one explicit per-call base
    key; per-gate/per-tensor sub-keys derived by the documented convention),
    so callers never fold keys ad hoc and rewire events can draw from the
    same convention.  Stacked networks fold the layer index into the base
    key first (`stacked_rtrl.make_stacked_masks`)."""
    def bernoulli(key, shape):
        if block == 1:
            return (jax.random.uniform(key, shape) >= sparsity).astype(jnp.float32)
        bshape = tuple(-(-s // block) for s in shape)
        coarse = (jax.random.uniform(key, bshape) >= sparsity).astype(jnp.float32)
        # index the coarse grid instead of jnp.kron: O(shape) gather, no
        # [bshape * block^2] intermediate, and no trailing crop
        return coarse[jnp.arange(shape[0]) // block][:, jnp.arange(shape[1]) // block]

    gates = mask_gates(cfg.kind)
    keys = gate_param_keys(key, gates)
    masks = {}
    for g in gates:
        masks[g] = {
            "W": bernoulli(keys[g]["W"], (cfg.n_in, cfg.n_hidden)) if mask_input
            else jnp.ones((cfg.n_in, cfg.n_hidden)),
            "R": bernoulli(keys[g]["R"], (cfg.n_hidden, cfg.n_hidden)),
            "b": jnp.ones((cfg.n_hidden,)),
        }
    masks["theta"] = jnp.ones((cfg.n_hidden,))
    masks["out"] = None          # readout stays dense
    return masks


def apply_masks(params: Tree, masks: Tree) -> Tree:
    # walk the mask tree (None = leave whole subtree dense, e.g. 'out')
    def leaf(m, p):
        return p if m is None else jax.tree.map(
            lambda pi, mi: pi * mi.astype(pi.dtype), p, m)
    return jax.tree.map(
        lambda m, p: p if m is None else p * m.astype(p.dtype),
        masks, params, is_leaf=lambda x: x is None)


def mask_counts(masks: Tree) -> tuple:
    """(nonzero, total) entries over the maskable recurrent params — the
    single source of the 'which params are maskable' rule (W/R; not bias,
    theta, or the readout)."""
    tot, nz = 0.0, 0.0
    for g, sub in masks.items():
        if g in ("out", "theta") or sub is None:
            continue
        for k in ("W", "R"):
            tot += sub[k].size
            nz += sub[k].sum()
    return nz, tot


def omega_tilde(masks: Tree) -> jax.Array:
    """Measured parameter density (over maskable recurrent params)."""
    nz, tot = mask_counts(masks)
    return nz / tot


# ---------------------------------------------------------------------------
# Closed-form per-step partials — these live in `repro.cells.egru` now (the
# cell zoo owns per-architecture math); re-exported here because the flat
# layout, the compact steps, and every historical consumer import them from
# this module.
# ---------------------------------------------------------------------------

from repro.cells.egru import (_activation, _cell_partials_impl,  # noqa: E402,F401
                              _gru_forward, cell_partials, cell_partials_full)


# ---------------------------------------------------------------------------
# Influence-matrix state
# ---------------------------------------------------------------------------

def init_influence(cfg: EGRUConfig, batch: int) -> Tree:
    n, m1 = cfg.n_hidden, cfg.n_in + cfg.n_hidden + 1
    if cfg.kind == "rnn":
        return {"v": jnp.zeros((batch, n, n, m1 + 1), jnp.float32)}
    return {g: jnp.zeros((batch, n, n, m1), jnp.float32) for g in ("u", "r", "z")} \
        | {"theta": jnp.zeros((batch, n, n), jnp.float32)}


def influence_update(cfg: EGRUConfig, M: Tree, hp, Jhat, mbar, masks=None):
    """M_t = D(hp) [ J-hat M_{t-1} + Mbar-hat ]   — Eq. (10) exactly."""
    n = cfg.n_hidden
    idx = jnp.arange(n)

    def jm(Mg):   # [B,n,n,m] or [B,n,n]
        if Mg.ndim == 4:
            return jnp.einsum("bkl,blqm->bkqm", Jhat, Mg)
        return jnp.einsum("bkl,blq->bkq", Jhat, Mg)

    def gmask(g):
        if masks is None or g not in masks:
            return None
        mk = masks[g]
        return jnp.concatenate([mk["W"].T, mk["R"].T,
                                jnp.ones((n, 1))], axis=1)    # [n(q), m]

    new = {}
    if cfg.kind == "rnn":
        T = jm(M["v"])
        add = jnp.einsum("bq,bm->bqm", mbar["v_diag_coef"],
                         mbar["v_g"])                          # [B,n(q),m]
        mk = gmask("v")
        if mk is not None:
            mk = jnp.concatenate([mk, jnp.ones((n, 1))], axis=1)  # theta col
            add = add * mk[None]
        T = T.at[:, idx, idx, :].add(add)
        new["v"] = hp[:, :, None, None] * T
        return new

    for g in ("u", "z"):
        T = jm(M[g])
        add = jnp.einsum("bq,bm->bqm", mbar[f"{g}_diag_coef"], mbar[f"{g}_g"])
        mk = gmask(g)
        if mk is not None:
            add = add * mk[None]
        T = T.at[:, idx, idx, :].add(add)
        new[g] = hp[:, :, None, None] * T
    # r gate: dense (k,q) coupling through R_z
    T = jm(M["r"])
    add = jnp.einsum("bkq,bm->bkqm", mbar["r_coef"], mbar["r_g"])
    mk = gmask("r")
    if mk is not None:
        add = add * mk[None, None]
    new["r"] = hp[:, :, None, None] * (T + add)
    # theta: dv_k/dtheta_q = -delta_kq
    Tt = jm(M["theta"])
    Tt = Tt.at[:, idx, idx].add(-1.0)
    new["theta"] = hp[:, :, None] * Tt
    return new


def influence_grads(cfg: EGRUConfig, M: Tree, cbar: jax.Array) -> Tree:
    """dL_t/dw += cbar_t^T M_t, mapped back to parameter structure."""
    n, n_in = cfg.n_hidden, cfg.n_in
    out = {}

    def split_g(gw):   # [q, m] -> dict(W [n_in,n], R [n,n], b [n])
        return {"W": gw[:, :n_in].T, "R": gw[:, n_in:n_in + n].T,
                "b": gw[:, n_in + n]}

    if cfg.kind == "rnn":
        gw = jnp.einsum("bk,bkqm->qm", cbar, M["v"])
        out["v"] = split_g(gw)
        out["theta"] = gw[:, -1]
        return out
    for g in ("u", "r", "z"):
        gw = jnp.einsum("bk,bkqm->qm", cbar, M[g])
        out[g] = split_g(gw)
    out["theta"] = jnp.einsum("bk,bkq->q", cbar, M["theta"])
    return out


# ---------------------------------------------------------------------------
# Flat influence layout: all gates in one [B, n, P] buffer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static column-layout descriptor of the flat influence buffer.

    Column  gate_offset(g) + q * m + j  holds  d a_k / d (j-th param of unit
    q's gate-g group), groups ordered (W col, R col, bias[, theta]).  For
    'rnn' theta is folded into the per-unit group (j == m-1); for 'gru' theta
    gets its own trailing n-column block.  P == p (the recurrent parameter
    count); buffers are allocated at P_pad (next LANE multiple) so the last
    dim is always tile-aligned — padding columns are permanently dead."""
    kind: str
    n: int
    n_in: int
    gates: tuple
    m: int                 # per-gate per-unit parameter-group width
    P: int                 # logical column count (== cfg.n_rec_params)
    P_pad: int             # P rounded up to a LANE multiple
    influence_dtype: str = "float32"   # carry dtype ("float32" | "bfloat16")

    def gate_offset(self, g: str) -> int:
        return self.gates.index(g) * self.n * self.m

    @property
    def theta_offset(self) -> int:          # gru only: trailing theta block
        return len(self.gates) * self.n * self.m

    @property
    def carry_dtype(self) -> jnp.dtype:
        return influence_carry_dtype(self.influence_dtype)


INFLUENCE_DTYPES = ("float32", "bfloat16")


def influence_carry_dtype(name: str) -> jnp.dtype:
    """Resolve the influence-carry dtype string.  The carry may be stored
    bf16 (half the per-stream bytes and bandwidth); every contraction still
    accumulates in f32 (`preferred_element_type`) so only the per-step
    round-off of the stored values is bf16-bounded."""
    if name in ("float32", "f32"):
        return jnp.float32
    if name in ("bfloat16", "bf16"):
        return jnp.bfloat16
    raise ValueError(f"influence_dtype {name!r} not in {INFLUENCE_DTYPES}")


def flat_layout(cfg: EGRUConfig,
                influence_dtype: str = "float32") -> FlatLayout:
    n, n_in = cfg.n_hidden, cfg.n_in
    if cfg.kind == "rnn":
        gates, m = ("v",), n_in + n + 2              # W, R, b, theta
        P = n * m
    else:
        gates, m = ("u", "r", "z"), n_in + n + 1     # W, R, b
        P = 3 * n * m + n                            # + theta block
    assert P == cfg.n_rec_params, (P, cfg.n_rec_params)
    P_pad = -(-P // LANE) * LANE
    return FlatLayout(cfg.kind, n, n_in, gates, m, P, P_pad, influence_dtype)


def init_influence_flat(layout: FlatLayout, batch: int) -> jax.Array:
    return jnp.zeros((batch, layout.n, layout.P_pad), layout.carry_dtype)


def _flat_col_mask_np(layout: FlatLayout, masks: Tree | None) -> np.ndarray:
    """Host (numpy) [P] column liveness — the single source `flat_col_mask`
    pads/uploads and `build_col_layout` consumes directly (rewire events
    rebuild layouts on the host; no device round trips)."""
    if masks is None:
        return np.ones((layout.P,), np.float32)
    n = layout.n
    parts = []
    for g in layout.gates:
        mk = masks[g]
        cols = [np.asarray(mk["W"]).T, np.asarray(mk["R"]).T,
                np.ones((n, 1), np.float32)]
        if layout.kind == "rnn":
            cols.append(np.ones((n, 1), np.float32))     # theta column
        parts.append(np.concatenate(cols, axis=1).reshape(-1))
    if layout.kind != "rnn":
        parts.append(np.ones((n,), np.float32))          # theta block
    return np.concatenate(parts).astype(np.float32)


def flat_col_mask(layout: FlatLayout, masks: Tree | None) -> jax.Array:
    """[P_pad] column liveness from the fixed parameter masks (Sec. 5).

    Padding columns are dead, so block-granular backends skip whole padded
    column blocks even without parameter sparsity."""
    live = jnp.asarray(_flat_col_mask_np(layout, masks))
    return jnp.pad(live, (0, layout.P_pad - layout.P))


def flat_jmask(cfg: EGRUConfig, masks: Tree | None) -> jax.Array | None:
    """Static [n, n] sparsity pattern of J-hat in R layout ([l, k]), or None.

    J inherits the masks' pattern (Sec. 5): for 'rnn' J-hat = R^T exactly;
    for 'gru' the three R paths union with the diagonal (1-u) term and the
    two-hop r-path  R_r @ R_z."""
    if masks is None:
        return None
    n = cfg.n_hidden
    if cfg.kind == "rnn":
        return (masks["v"]["R"] > 0).astype(jnp.float32)
    mu, mr, mz = (masks[g]["R"] for g in ("u", "r", "z"))
    pat = mu + mz + (mr @ mz) + jnp.eye(n)
    return (pat > 0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Column compaction: the fixed masks make the live (q, m)-column set STATIC,
# so the flat parameter axis itself is carried at compact width Pc ~= w~ P —
# the paper's omega~ memory factor realised physically, composing with the
# row compaction's beta~ factor (dual row x column compaction).
# ---------------------------------------------------------------------------

# gate ids on the compact column axis: layout.gates order, then theta block
COL_GATE_THETA = 3        # 'gru' trailing theta block ('rnn' folds theta in m)


@dataclasses.dataclass(frozen=True)
class ColLayout:
    """Static live-column map of a (possibly stacked) flat parameter axis.

    Compact column c < Pc holds flat column src[c] of the full P_pad-wide
    axis; (layer, gate, q, j) decompose it into the owning layer, the gate
    block (gates order, COL_GATE_THETA = gru theta block), the unit index q
    and the within-group parameter index j — everything `flat_mbar_rows_cols`
    needs to build the immediate influence DIRECTLY at compact width, never
    materializing the P-wide form.  Columns are kept in ascending src order;
    Pc_pad rounds up to a LANE multiple (pad columns dead, live = 0).
    Built eagerly (host numpy) from the concrete masks at init — masks are
    fixed (Sec. 6), so this is a one-off."""
    Pc: int                # live column count  (~= w~ P)
    Pc_pad: int            # Pc rounded up to a LANE multiple
    P_pad: int             # width of the full flat axis this compacts
    src: jax.Array         # [Pc_pad] int32 original flat column (pad: P_pad)
    layer: jax.Array       # [Pc_pad] int32 owning layer (pad: -1)
    gate: jax.Array        # [Pc_pad] int32 gate id within layer (pad: -1)
    q: jax.Array           # [Pc_pad] int32 unit index within layer
    j: jax.Array           # [Pc_pad] int32 within-group param index
    live: jax.Array        # [Pc_pad] float32 1/0 (pad columns 0)
    influence_dtype: str = "float32"   # carry dtype of [B, K, Pc_pad] vals

    @property
    def carry_dtype(self) -> jnp.dtype:
        return influence_carry_dtype(self.influence_dtype)


def _decompose_columns(layout: FlatLayout):
    """(gate, q, j) int arrays [P] for one layer's local flat columns."""
    n, m = layout.n, layout.m
    c = np.arange(layout.P)
    if layout.kind == "rnn":
        return np.zeros_like(c), (c // m), (c % m)
    gate = np.minimum(c // (n * m), COL_GATE_THETA)
    rem = c % (n * m)
    q = np.where(gate < COL_GATE_THETA, rem // m, c - len(layout.gates) * n * m)
    j = np.where(gate < COL_GATE_THETA, rem % m, 0)
    return gate, q, j


def build_col_layout(parts, P_pad: int,
                     influence_dtype: str = "float32") -> ColLayout:
    """ColLayout over concatenated per-layer column blocks.

    parts: [(FlatLayout, masks-or-None, column offset, layer id)] — one
    entry for a single-layer axis, one per layer for the stacked axis."""
    srcs, layers, gates, qs, js = [], [], [], [], []
    for lay, mk, off, lid in parts:
        live = _flat_col_mask_np(lay, mk) > 0
        g, q, j = _decompose_columns(lay)
        idx = np.nonzero(live)[0]
        srcs.append(idx + off)
        layers.append(np.full(idx.size, lid))
        gates.append(g[idx])
        qs.append(q[idx])
        js.append(j[idx])
    src = np.concatenate(srcs)
    Pc = int(src.size)
    Pc_pad = max(LANE, -(-Pc // LANE) * LANE)
    pad = Pc_pad - Pc

    def col(a, fill):
        return jnp.asarray(np.concatenate(
            [a, np.full(pad, fill)]).astype(np.int32))

    return ColLayout(
        Pc=Pc, Pc_pad=Pc_pad, P_pad=P_pad,
        src=col(src, P_pad), layer=col(np.concatenate(layers), -1),
        gate=col(np.concatenate(gates), -1), q=col(np.concatenate(qs), 0),
        j=col(np.concatenate(js), 0),
        live=jnp.asarray((np.arange(Pc_pad) < Pc).astype(np.float32)),
        influence_dtype=influence_dtype)


def col_layout(layout: FlatLayout, masks: Tree | None,
               influence_dtype: str | None = None) -> ColLayout:
    """Single-layer live-column map (masks=None -> all P columns live)."""
    return build_col_layout(
        [(layout, masks, 0, 0)], layout.P_pad,
        layout.influence_dtype if influence_dtype is None else influence_dtype)


def flat_col_density(layout: FlatLayout, masks: Tree | None) -> float:
    """Live fraction of the P logical parameter columns — the omega~ factor
    the column compaction realises (Pc == flat_col_density * P).  Shares the
    ONE live-fraction definition with the byte accounting in
    `repro.core.costs.carry_footprint`."""
    from repro.core.costs import live_col_fraction
    live = int(_flat_col_mask_np(layout, masks).sum())
    return live_col_fraction(live, layout.P)


def flat_to_cols(cl: ColLayout, x: jax.Array) -> jax.Array:
    """Gather the live columns: [..., P_pad] -> [..., Pc_pad] (pad cols 0)."""
    safe = jnp.clip(cl.src, 0, cl.P_pad - 1)
    return jnp.take(x, safe, axis=-1) * cl.live


def cols_to_flat(cl: ColLayout, x: jax.Array) -> jax.Array:
    """Scatter back to the full axis: [..., Pc_pad] -> [..., P_pad].

    Dead columns of the full axis come back exactly zero — with
    `flat_to_cols` this is a lossless round trip on column-masked buffers."""
    src = jnp.where(cl.live > 0, cl.src, cl.P_pad)      # pad -> sentinel col
    out = jnp.zeros(x.shape[:-1] + (cl.P_pad + 1,), x.dtype)
    out = out.at[..., src].add(x * cl.live)
    return out[..., :cl.P_pad]


def flat_mbar_rows_cols(cfg: EGRUConfig, layout: FlatLayout, cl: ColLayout,
                        mbar: Tree, safe_new: jax.Array, *,
                        layer: int = 0) -> jax.Array:
    """M-bar rows at the active row indices, DIRECTLY at compact column
    width: [B, K, Pc_pad] — the column-compact sibling of `flat_mbar_rows`.

    Cost is K * Pc elementwise (+ the r-gate gather), never touching the
    P-wide axis: the w~ factor applies to the immediate-influence build too,
    not only the J contraction.  Diagonal gates (u/z, rnn v) and theta only
    hit columns whose unit q equals the row's unit; the r gate couples all
    live q through R_z, read off the already-computed mbar['r_coef'].
    `layer` selects this layer's columns of a stacked axis (others -> 0)."""
    n, m = layout.n, layout.m
    B, K = safe_new.shape
    sel = (cl.layer == layer) & (cl.live > 0)           # [Pc_pad]
    q = jnp.clip(jnp.where(sel, cl.q, 0), 0, n - 1)
    j = jnp.clip(jnp.where(sel, cl.j, 0), 0, m - 1)
    gate = jnp.where(sel, cl.gate, -1)
    match = (q[None, None, :] == safe_new[:, :, None])  # [B, K, Pc_pad]
    if cfg.kind == "rnn":
        Cdiag = (mbar["v_diag_coef"][:, q] * mbar["v_g"][:, j]
                 * sel.astype(jnp.float32))             # [B, Pc_pad]
        return match * Cdiag[:, None, :]
    gu, gr, gz = (layout.gates.index(g) for g in ("u", "r", "z"))
    Cdiag = jnp.where(
        gate == gu, mbar["u_diag_coef"][:, q] * mbar["u_g"][:, j],
        jnp.where(gate == gz, mbar["z_diag_coef"][:, q] * mbar["z_g"][:, j],
                  jnp.where(gate == COL_GATE_THETA, -1.0, 0.0)))
    out = match * Cdiag[:, None, :]
    # r gate: value[b, k, c] = r_coef[b, row_k, q(c)] * r_g[b, j(c)]
    bidx = jnp.arange(B)[:, None]
    rc_rows = mbar["r_coef"][bidx, safe_new]            # [B, K, n]
    rc = jnp.take_along_axis(
        rc_rows, jnp.broadcast_to(q[None, None, :], (B, K, cl.Pc_pad)),
        axis=2)
    return out + rc * (mbar["r_g"][:, j] * (gate == gr))[:, None, :]


def flat_mbar_cols(cfg: EGRUConfig, layout: FlatLayout, cl: ColLayout,
                   mbar: Tree, *, layer: int = 0) -> jax.Array:
    """Full-row immediate influence at compact column width [B, n, Pc_pad]
    (hp-ungated) — feeds the dual-compacted Pallas/dense full-row paths."""
    B = (mbar["v_g"] if cfg.kind == "rnn" else mbar["u_g"]).shape[0]
    rows = jnp.broadcast_to(jnp.arange(layout.n)[None], (B, layout.n))
    return flat_mbar_rows_cols(cfg, layout, cl, mbar, rows, layer=layer)


def flat_mbar(cfg: EGRUConfig, layout: FlatLayout, mbar: Tree,
              col_mask: jax.Array | None = None, *, offset: int = 0,
              total_pad: int | None = None) -> jax.Array:
    """Immediate influence M-bar-hat in flat layout [B, n, total_pad]
    (hp-ungated); total_pad defaults to the layer's own P_pad.

    u/z (and rnn v) gates are diagonal in (k, q); the r gate couples densely
    through R_z; theta is -I.  `offset` places the layer's P columns inside a
    wider stacked buffer (core/stacked_rtrl); `col_mask` spans the full
    width."""
    n, m = layout.n, layout.m
    idx = jnp.arange(n)
    blocks = []
    if cfg.kind == "rnn":
        B = mbar["v_g"].shape[0]
        add = mbar["v_diag_coef"][:, :, None] * mbar["v_g"][:, None, :]
        M4 = jnp.zeros((B, n, n, m)).at[:, idx, idx, :].set(add)
        blocks.append(M4.reshape(B, n, n * m))
    else:
        B = mbar["u_g"].shape[0]
        for g in layout.gates:
            if g == "r":
                M4 = jnp.einsum("bkq,bm->bkqm", mbar["r_coef"], mbar["r_g"])
            else:
                add = (mbar[f"{g}_diag_coef"][:, :, None]
                       * mbar[f"{g}_g"][:, None, :])
                M4 = jnp.zeros((B, n, n, m)).at[:, idx, idx, :].set(add)
            blocks.append(M4.reshape(B, n, n * m))
        blocks.append(-jnp.broadcast_to(jnp.eye(n)[None], (B, n, n)))
    flat = jnp.concatenate(blocks, axis=-1)
    total = layout.P_pad if total_pad is None else total_pad
    flat = jnp.pad(flat, ((0, 0), (0, 0),
                          (offset, total - offset - layout.P)))
    if col_mask is not None:
        flat = flat * col_mask[None, None, :]
    return flat


def flat_mbar_rows(cfg: EGRUConfig, layout: FlatLayout, mbar: Tree,
                   safe_new: jax.Array, col_mask: jax.Array | None = None,
                   *, offset: int = 0, total_pad: int | None = None):
    """M-bar rows gathered at the active row indices: [B, K, total_pad].

    The dense [B, n, P] (i.e. [B, n, n, m]) immediate-influence tensor is
    never materialized on the compact path; dead slots (safe_new clamped)
    produce garbage rows that the caller gates to zero through hp."""
    n, m = layout.n, layout.m
    B, K = safe_new.shape
    bidx = jnp.arange(B)[:, None]
    slot = jnp.arange(K)[None, :]
    blocks = []
    if cfg.kind == "rnn":
        add = (mbar["v_diag_coef"][bidx, safe_new][:, :, None]
               * mbar["v_g"][:, None, :])                       # [B, K, m]
        M4 = jnp.zeros((B, K, n, m)).at[bidx, slot, safe_new, :].set(add)
        blocks.append(M4.reshape(B, K, n * m))
    else:
        for g in layout.gates:
            if g == "r":
                coef = mbar["r_coef"][bidx, safe_new]           # [B, K, n]
                M4 = jnp.einsum("bkq,bm->bkqm", coef, mbar["r_g"])
            else:
                add = (mbar[f"{g}_diag_coef"][bidx, safe_new][:, :, None]
                       * mbar[f"{g}_g"][:, None, :])
                M4 = jnp.zeros((B, K, n, m)).at[bidx, slot, safe_new, :].set(add)
            blocks.append(M4.reshape(B, K, n * m))
        th = jnp.zeros((B, K, n)).at[bidx, slot, safe_new].set(-1.0)
        blocks.append(th)
    flat = jnp.concatenate(blocks, axis=-1)
    total = layout.P_pad if total_pad is None else total_pad
    flat = jnp.pad(flat, ((0, 0), (0, 0),
                          (offset, total - offset - layout.P)))
    if col_mask is not None:
        flat = flat * col_mask[None, None, :]
    return flat


def unflatten_flat_grads(cfg: EGRUConfig, layout: FlatLayout,
                         gw: jax.Array) -> Tree:
    """Flat gradient [P_pad] -> recurrent parameter tree (inverse layout)."""
    n, n_in, m = layout.n, layout.n_in, layout.m
    out: dict = {}
    for i, g in enumerate(layout.gates):
        gq = gw[i * n * m:(i + 1) * n * m].reshape(n, m)        # [q, m]
        out[g] = {"W": gq[:, :n_in].T, "R": gq[:, n_in:n_in + n].T,
                  "b": gq[:, n_in + n]}
        if cfg.kind == "rnn":
            out["theta"] = gq[:, -1]
    if cfg.kind != "rnn":
        out["theta"] = gw[layout.theta_offset:layout.theta_offset + layout.n]
    return out


def flat_compact_step(cfg: EGRUConfig, w: Tree, layout: FlatLayout,
                      a_prev: jax.Array, vals: jax.Array, idx_prev: jax.Array,
                      x_t: jax.Array, col_mask: jax.Array | None = None,
                      *, offset: int = 0, total_pad: int | None = None,
                      below: tuple | None = None,
                      cl: ColLayout | None = None, layer: int = 0):
    """One RTRL step with the influence carried row-compact in flat layout.

    vals [B, K, total_pad], idx_prev [B, K] (sentinel -1 = dead slot).
    Returns (a_new, hp, vals', idx' (-1 sentinel), count, overflow).  FLOPs
    of the update are K * K_prev * P — the paper's beta~(t) beta~(t-1) n^2 p
    made wall-clock-real; `repro.core.scaled_rtrl` and the "compact" backend
    of `sparse_rtrl_loss_and_grads` both run on this step.

    Stacked networks (core/stacked_rtrl): `offset`/`total_pad` place this
    layer's immediate-influence columns inside the stacked parameter axis,
    and `below=(vals_below, idx_below)` adds the cross-layer term
    B^(l) M^(l-1)_t — x_t is then the layer below's activity a^{l-1}_t and
    the input-Jacobian tiles B-hat are looked up at (new rows, active rows of
    the layer below), so the cross term costs K * K_below * P, event-sparse
    on both sides.

    DUAL compaction: with `cl` (a ColLayout over the same flat axis) the
    parameter axis is carried column-compact — vals are [B, K, Pc_pad], the
    M-bar rows are built directly at compact width (`flat_mbar_rows_cols`;
    `layer` names this layer's columns of a stacked axis) and the update
    costs K * K_prev * Pc ~= w~ beta~^2 n^2 p — the paper's COMBINED
    activity x parameter factor.  col_mask/offset/total_pad are ignored in
    this mode (liveness and placement live inside `cl`)."""
    from repro.kernels import compact as CK
    n = layout.n
    K = idx_prev.shape[1]
    with jax.named_scope("partials"):
        if below is None:
            a_new, hp, Jhat, mbar = cell_partials(cfg, w, a_prev, x_t)
            Bhat = None
        else:
            a_new, hp, Jhat, Bhat, mbar = cell_partials_full(cfg, w, a_prev,
                                                             x_t)
    with jax.named_scope("j_tile_gather"):
        idx_new, count = CK.compact_rows(hp != 0.0, K)
        safe_new = jnp.clip(idx_new, 0, n - 1)
        # rnn J-hat = R^T: lookup tiles straight from R, never building
        # [B, n, n]
        R = w["v"]["R"] if cfg.kind == "rnn" else None
        Jgg = CK.gather_j_tiles(None if R is not None else Jhat,
                                idx_new, idx_prev, R=R)
        hp_rows = CK.take_rows(hp, idx_new)
        if below is not None:
            vals_b, idx_b = below
            if cfg.kind == "rnn":
                # B-hat = W^T exactly: look tiles up from W
                Bgg = CK.gather_tiles(None, idx_new, idx_b, AT=w["v"]["W"])
            else:
                Bgg = CK.gather_tiles(Bhat, idx_new, idx_b)
    with jax.named_scope("mbar_rows"):
        if cl is not None:
            mbar_rows = flat_mbar_rows_cols(cfg, layout, cl, mbar, safe_new,
                                            layer=layer)
        else:
            mbar_rows = flat_mbar_rows(cfg, layout, mbar, safe_new, col_mask,
                                       offset=offset, total_pad=total_pad)
        if below is not None:
            mbar_rows = mbar_rows + jnp.einsum(
                "bkj,bjp->bkp", Bgg, vals_b,
                preferred_element_type=jnp.float32)
    with jax.named_scope("influence_update"):
        Mc, overflow = CK.compact_update(Jgg, vals, mbar_rows, hp_rows,
                                         idx_new, count, K)
    return a_new, hp, Mc.vals, Mc.idx, Mc.count, overflow


def flat_compact_fused_step(cfg: EGRUConfig, w: Tree, layout: FlatLayout,
                            a_prev: jax.Array, vals: jax.Array,
                            idx_prev: jax.Array, x_t: jax.Array, *,
                            below: tuple | None = None, cl: ColLayout,
                            layer: int = 0, segments: tuple | None = None,
                            use_kernel: bool | None = None,
                            interpret: bool | None = None):
    """`flat_compact_step`, fused: one invocation per influence update.

    Same contract as the dual-compact mode of `flat_compact_step` (cl is
    REQUIRED; returns (a_new, hp, vals', idx', count, overflow)), but the
    J-tile gather, the [K x K'] x [K' x Pc] contraction, the M-bar add and
    the hp diagonal scale run as ONE fused kernel with capacity ragged PER
    EXAMPLE — executed compute is Sigma_b K_b K'_b Pc, not B K^2 Pc (see
    `repro.kernels.compact_fused`).  The carry dtype follows vals (opt-in
    bf16 with f32 accumulation).

    `segments` is the static gate-segment table from
    `compact_fused.fused_segments(layout, cl, layer)` — pass the one built
    at learner init; built on the fly otherwise (requires a concrete cl,
    so this backend rejects runtime-rewired ColLayouts).  use_kernel: None
    = auto (the Pallas grid on TPU, the blocked-switch XLA lowering
    elsewhere); True forces the Pallas kernel (interpret-mode off-TPU —
    how the parity tests drive it).  On TPU the kernel is the only path:
    a capacity it cannot tile raises rather than falling back to XLA."""
    from repro.kernels import compact as CK
    from repro.kernels import compact_fused as CF
    n = layout.n
    K = idx_prev.shape[1]
    if segments is None:
        segments = CF.fused_segments(layout, cl, layer=layer)
    if use_kernel is None:
        use_kernel = CF._on_tpu()
    with jax.named_scope("partials"):
        if below is None:
            a_new, hp, Jhat, mbar = cell_partials(cfg, w, a_prev, x_t)
            Bhat = None
        else:
            a_new, hp, Jhat, Bhat, mbar = cell_partials_full(cfg, w, a_prev,
                                                             x_t)
    with jax.named_scope("j_tile_gather"):
        idx_new, count = CK.compact_rows(hp != 0.0, K)
        safe_new = jnp.clip(idx_new, 0, n - 1)
        hp_rows = CK.take_rows(hp, idx_new)
        count_prev = jnp.sum(idx_prev >= 0, axis=1)
        overflow = jnp.maximum(count - K, 0)
        count_new = jnp.minimum(count, K)
        # rnn J-hat = R^T: lookup tiles straight from R, never building
        # [B, n, n]
        R = w["v"]["R"] if cfg.kind == "rnn" else None
        Jgg = CK.gather_j_tiles(None if R is not None else Jhat,
                                idx_new, idx_prev, R=R)
        Bgg = None
        if below is not None:
            vals_b, idx_b = below
            AT = w["v"]["W"] if cfg.kind == "rnn" else None
            Bgg = CK.gather_tiles(None if AT is not None else Bhat,
                                  idx_new, idx_b, AT=AT)
    if use_kernel:
        # TPU grid over the tiles looked up in XLA; M-bar rows built at compact
        # width, the cross-layer injection folded into them
        with jax.named_scope("mbar_rows"):
            mbar_rows = flat_mbar_rows_cols(cfg, layout, cl, mbar, safe_new,
                                            layer=layer)
            if Bgg is not None:
                mbar_rows = mbar_rows + jnp.einsum(
                    "bkj,bjp->bkp", Bgg, vals_b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        with jax.named_scope("influence_update"):
            new_vals = CF.fused_update_pallas(
                Jgg, vals, mbar_rows, hp_rows, count_new, count_prev,
                interpret=interpret)
        return a_new, hp, new_vals, idx_new, count_new, overflow
    # XLA lowering: per-example blocked dots over a static capacity ladder,
    # M-bar generated inline at each gate's compact column segment
    with jax.named_scope("influence_update"):
        new_vals = CF.fused_update_blocks(
            mbar, safe_new, hp_rows, Jgg, vals, count_new, count_prev,
            segments, hp_full=hp, n=n,
            below=None if Bgg is None else (Bgg, vals_b))
    return a_new, hp, new_vals, idx_new, count_new, overflow


def capacity_K(n: int, capacity: float) -> int:
    """Static row capacity: ceil(capacity * n), 8-aligned, capped at n."""
    return max(8, min(n, -(-int(math.ceil(capacity * n)) // 8) * 8))


# ---------------------------------------------------------------------------
# Full sequence: loss + grads + sparsity stats (exact, memory O(B n p))
# ---------------------------------------------------------------------------

BACKENDS = ("dense", "pallas", "compact", "compact_fused")


def sparse_rtrl_loss_and_grads(cfg: EGRUConfig, params: Tree, xs: jax.Array,
                               labels: jax.Array, masks: Tree | None = None,
                               *, backend: str = "dense",
                               capacity: float = 1.0,
                               interpret: bool | None = None,
                               col_compact: bool | None = None,
                               influence_dtype: str = "float32"):
    """Structured exact RTRL. Returns (loss, grads, stats).

    backend selects the influence-update execution strategy (see module
    docstring); all backends are exact — "compact" additionally requires the
    static row capacity (ceil(capacity * n), 8-aligned) to cover the active
    rows, and reports dropped rows in stats["overflow"].  interpret forces
    the Pallas kernel's interpret mode (None = auto: interpret off-TPU).

    col_compact carries the parameter axis of the influence at the STATIC
    compact width Pc ~= w~ P derived from the fixed masks (pallas/compact
    backends; exact — a representation change, not an approximation).  The
    default None enables it exactly when masks are given; the flat gradient
    is scattered back to the full axis once, after the scan.

    stats carries per-step alpha/beta (and previous-step beta) so
    `repro.core.costs` can integrate the paper's compute-adjusted iterations.

    This is a thin whole-sequence scan over the streaming Learner API
    (`repro.core.learner.SparseLearner`) — the per-step engine is the
    learner's `step`, shared bit-for-bit with online training.
    """
    from repro.core.learner import LearnerSpec, make_learner, scan_learner
    learner = make_learner(LearnerSpec(
        engine="sparse", cfg=cfg, backend=backend, capacity=capacity,
        interpret=interpret, col_compact=col_compact,
        influence_dtype=influence_dtype))
    return scan_learner(learner, params, masks, xs, labels)


def _row_density(M: Tree) -> jax.Array:
    """Fraction of nonzero rows of the influence matrix (memory measure)."""
    dens = []
    for g, Mg in M.items():
        flat = Mg.reshape(Mg.shape[0], Mg.shape[1], -1)
        dens.append(jnp.mean(jnp.any(flat != 0.0, axis=2)))
    return jnp.stack(dens).mean()


def influence_col_density(M: Tree) -> jax.Array:
    """Fraction of nonzero (q, m) columns — parameter-sparsity invariant."""
    dens = []
    for g, Mg in M.items():
        flat = Mg.reshape(Mg.shape[0] * Mg.shape[1], -1)
        dens.append(jnp.mean(jnp.any(flat != 0.0, axis=0)))
    return jnp.stack(dens).mean()
