"""Streaming-first Learner API: one protocol over every gradient engine.

The paper's central claim is that combined activity and parameter sparsity
makes *online* RTRL practical — memory independent of sequence length,
gradients available at every step.  This module is the seam that makes that
expressible: every gradient engine in the repo (exact sparse RTRL in all its
backends, the stacked block engine, the scaled/sharded carry, the diagonal
eligibility traces, the SnAp approximations, and a BPTT sequence-adapter
oracle) is reachable through ONE protocol:

    learner = make_learner(LearnerSpec(engine=..., cfg=..., backend=...))
    carry   = learner.init(params, masks, (x_0, y_0), t_total=T)
    carry, out = learner.step(carry, x_t, y_t)    # any number of times
    grads   = learner.grads(carry)                # whenever a consumer wants
    carry   = learner.reset_grads(carry, new_params)   # after an update

Contract:

  * ``carry`` is a pytree (a dict) holding EVERYTHING that evolves: the
    current ``params``, the recurrent activity, the influence/trace state,
    the gradient accumulators (``gw``/``gout``), the running ``loss`` and
    the per-step loss scale ``t_total``.  It is O(1) in stream length for
    every RTRL engine (the point of RTRL) and is directly checkpointable —
    `repro.runtime.online.OnlineTrainer` saves/restores it mid-stream.
  * ``step`` consumes one timestep (x_t, y_t) and returns the new carry
    plus a :class:`StepOut` — instantaneous loss, readout logits, per-step
    stats, and (with ``spec.per_step_grads``) this step's gradient
    contribution alone.
  * ``grads`` finalizes the accumulated gradient into the parameter-tree
    structure (column-compact flat accumulators are scattered back here,
    once — not per step).
  * ``reset_grads`` zeroes the accumulators (and swaps in updated params)
    WITHOUT touching the influence state: the standard mid-sequence-update
    regime of online RTRL (Irie et al., 2023).  The BPTT adapter instead
    restarts its window here — truncated BPTT, the baseline RTRL frees you
    from.

The legacy whole-sequence entry points (`sparse_rtrl_loss_and_grads`,
`stacked_rtrl_loss_and_grads`, `scaled_rtrl.rtrl_grads`,
`diag_rtrl.rtrl_loss_and_grads`, `snap.snap_loss_and_grads`) are thin
`jax.lax.scan` wrappers over these learners (``scan_learner``) — the
per-step ops are literally the same code, so the refactor is bit-for-bit
(tested in tests/test_online.py by replaying the stream path against the
whole-sequence path).

Loss convention: per-step loss is ``xent(readout(a_t), y_t) / t_total``
with ``t_total`` carried as a scalar.  Legacy wrappers pass ``t_total=T``
(the historical mean-over-sequence loss); online consumers pass the update
window k so each window's accumulated loss is a window mean.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Protocol

import jax
import jax.numpy as jnp

from repro.cells import resolve_cell
from repro.core import cells, sparse_rtrl as SP, stacked_rtrl as ST
from repro.core.cells import EGRUConfig, StackedEGRUConfig

Tree = Any


class StepOut(NamedTuple):
    """What one online step yields to the consumer."""
    loss: jax.Array            # instantaneous loss L_t (1/t_total-scaled)
    readout: jax.Array | None  # logits [B, n_out] at this step (None: n/a)
    stats: dict                # per-step sparsity/overflow stats (engine-specific)
    grads: Tree | None = None  # THIS step's gradient term (spec.per_step_grads)


@dataclasses.dataclass(frozen=True)
class LearnerSpec:
    """Everything needed to construct a learner — the one spec the serving
    and scale layers configure engines through.

    engine     'sparse' | 'stacked' | 'scaled' | 'diag' | 'diag_exact' |
               'eprop' | 'snap' | 'bptt'
    cfg        the engine's config object — resolved to a zoo cell via
               `repro.cells.resolve_cell` where the engine is cell-agnostic:
                 sparse/snap/bptt  EGRUConfig
                 stacked           StackedEGRUConfig (or EGRUConfig + layers)
                 scaled            scaled_rtrl.ScaledRTRLConfig
                 diag              diag_rtrl.DiagCellConfig
                 diag_exact        any jac_kind="diagonal" or
                                   "head_diagonal" cell config
                                   (cells.rglru.RGLRUCellConfig,
                                   DiagCellConfig,
                                   cells.mamba2.GraniteHybridConfig)
                 eprop             cells.snn.SNNConfig
    backend    sparse/stacked influence execution:
               dense | pallas | compact | compact_fused
    col_compact carry the influence parameter axis column-compact
               (None = auto: masks given and backend != dense;
               compact_fused always carries column-compact)
    influence_dtype  carry dtype of the influence state: 'float32' |
               'bfloat16' (bf16 halves the per-stream carry bytes; every
               contraction still accumulates f32)
    layers     stacked depth when cfg is a plain EGRUConfig
    capacity   compact-backend static row-capacity fraction
    interpret  force Pallas interpret mode (None = auto)
    order      SnAp order (1 or 2)
    horizon    bptt adapter window length (None = round(t_total) at init)
    per_step_grads  also emit each step's own gradient term in StepOut
    delegate_single_layer  stacked L=1 runs the single-layer engine
               (bit-for-bit the historical delegation)
    rewirable  support prune-and-regrow rewire events (repro.sparsity):
               all mask-derived state (mask tree, column maps, J pattern)
               moves INTO the carry so `rewire(carry, event_key)` can swap
               it between jitted chunks without retracing — requires masks
               at init; sparse/stacked/scaled engines only
    """
    engine: str = "sparse"
    cfg: Any = None
    backend: str = "dense"
    col_compact: bool | None = None
    influence_dtype: str = "float32"
    layers: int = 1
    capacity: float = 1.0
    interpret: bool | None = None
    order: int = 1
    horizon: int | None = None
    per_step_grads: bool = False
    delegate_single_layer: bool = True
    rewirable: bool = False


class Learner(Protocol):
    """Structural protocol every engine learner satisfies."""
    spec: LearnerSpec

    def init(self, params: Tree, masks: Tree | None, batch: tuple,
             t_total: float = 1.0) -> Tree: ...

    def step(self, carry: Tree, x_t: jax.Array,
             y_t: jax.Array) -> tuple[Tree, StepOut]: ...

    def grads(self, carry: Tree) -> Tree: ...

    def reset_grads(self, carry: Tree, params: Tree | None = None) -> Tree: ...

    def params_of(self, carry: Tree) -> Tree: ...

    def rewire(self, carry: Tree, event_key: jax.Array, *,
               frac: float = 0.1, method: str = "rigl",
               block: int = 1) -> Tree: ...


def exact_matmuls(step):
    """Trace an exact engine's `step` with every matmul at HIGHEST precision.

    XLA:TPU runs an f32 dot at DEFAULT precision as one bf16 pass, which
    breaks the exactness the engines below claim (and can flip an EGRU event
    gate).  Under `jax.default_matmul_precision("highest")` the forward
    partials, the influence contractions (XLA einsums and the Pallas kernels'
    dots alike) and the gradient extraction all contract in f32.  XLA:CPU
    computes f32 dots either way, so CPU results are unchanged."""
    @functools.wraps(step)
    def wrapped(self, carry, x_t, y_t):
        with jax.default_matmul_precision("highest"):
            return step(self, carry, x_t, y_t)
    return wrapped


class _LearnerBase:
    """Shared carry conventions: dict carry with 'params', 'loss', 't_total'
    and gradient accumulators 'gw'/'gout'."""
    spec: LearnerSpec

    def rewire(self, carry: Tree, event_key: jax.Array, *,
               frac: float = 0.1, method: str = "rigl",
               block: int = 1) -> Tree:
        """Prune-and-regrow mask rewire event (repro.sparsity).  Defined for
        the exact sparse/stacked/scaled RTRL learners constructed with
        ``LearnerSpec(rewirable=True)``; everywhere else there is no mask
        state to evolve, so this is a hard error, not a silent no-op."""
        raise NotImplementedError(
            f"{type(self).__name__} has no dynamic-sparsity support: rewire "
            "is defined for the sparse/stacked/scaled exact-RTRL learners "
            "constructed with LearnerSpec(rewirable=True)")

    def opt_mask_of(self, carry: Tree) -> Tree:
        """The CURRENT mask tree in the optimizer's parameter structure
        (what `optim.optimizers.set_opt_mask` consumes after a rewire)."""
        raise NotImplementedError(
            f"{type(self).__name__} carries no mask state")

    def reset_grads(self, carry: Tree, params: Tree | None = None) -> Tree:
        carry = dict(carry)
        if params is not None:
            carry["params"] = params
        for k in ("gw", "gout"):
            if k in carry:
                carry[k] = jax.tree.map(jnp.zeros_like, carry[k])
        carry["loss"] = jnp.zeros_like(carry["loss"])
        return carry

    def params_of(self, carry: Tree) -> Tree:
        """The current parameters in the structure the OPTIMIZER sees (the
        structure `grads` returns) — learners whose carry holds an internal
        view override this."""
        return carry["params"]

    def _freeze_static(self, **kv):
        """Bind init-derived static structure (masks, layouts, horizon) to
        this learner instance ONCE.  A carry only makes sense against the
        structure it was built with, so re-initializing the same instance
        with different masks/settings would silently mis-map earlier carries
        — make a new learner via make_learner(spec) instead."""
        prev = getattr(self, "_frozen", None)
        if prev is None:
            self._frozen = kv
            return
        for k, v in kv.items():
            old = prev[k]
            same = old is v or (
                isinstance(v, (int, float, bool, type(None))) and old == v)
            if not same:
                raise ValueError(
                    f"learner already initialized with a different {k!r}; "
                    "carries are bound to the init-time structure — create "
                    "a fresh learner via make_learner(spec) instead")

    @staticmethod
    def _base_carry(params: Tree, t_total: float) -> dict:
        return {"params": params, "loss": jnp.float32(0),
                "t_total": jnp.float32(t_total)}

    @staticmethod
    def _inst_loss(po, ai, y_t, tt):
        return cells.xent(cells.readout({"out": po}, ai), y_t) / tt


# ---------------------------------------------------------------------------
# Exact single-layer sparse RTRL (dense / pallas / compact x col-compact)
# ---------------------------------------------------------------------------

_CL_FIELDS = ("src", "layer", "gate", "q", "j", "live")


def _cl_arrays(cl) -> dict:
    """The ColLayout's array fields as a carry-able dict — the static ints
    (Pc/Pc_pad/P_pad) stay on the learner because count-preserving rewire
    never changes them."""
    return {f: getattr(cl, f) for f in _CL_FIELDS}

class SparseLearner(_LearnerBase):
    """`repro.core.sparse_rtrl` as a streaming learner — all three backends,
    optionally dual (row x column) compact.  Exact.

    With ``spec.rewirable`` the mask-derived state (mask tree, column
    mask/map, J pattern) lives in ``carry["rw"]`` instead of on the
    instance, so `rewire` can evolve the masks between jitted chunks with
    every buffer SHAPE — and therefore every compiled step — unchanged
    (count-preserving prune-and-regrow keeps Pc static)."""

    def __init__(self, spec: LearnerSpec):
        if spec.backend not in SP.BACKENDS:
            raise ValueError(
                f"backend must be one of {SP.BACKENDS}, got {spec.backend!r}")
        if spec.backend == "compact_fused" and spec.rewirable:
            raise ValueError(
                "backend='compact_fused' compiles a static gate-segment "
                "table from the ColLayout, so runtime mask rewiring is not "
                "supported — use backend='compact' with rewirable=True")
        if (SP.influence_carry_dtype(spec.influence_dtype) != jnp.float32
                and spec.backend in ("dense", "pallas")):
            raise ValueError("influence_dtype='bfloat16' needs a compact "
                             "carry (backend 'compact' or 'compact_fused')")
        self.spec = spec
        self.cfg: EGRUConfig = spec.cfg
        self.cell = resolve_cell(spec.cfg)
        self.backend = spec.backend
        self._score_fn = None
        self._apply_fn = None

    def init(self, params, masks, batch, t_total: float = 1.0):
        cfg = self.cfg
        x0, y0 = batch
        B = x0.shape[0]
        col_compact = self.spec.col_compact
        if self.backend == "compact_fused":
            if col_compact is False:
                raise ValueError("compact_fused always carries the "
                                 "parameter axis column-compact")
            col_compact = True
        elif col_compact is None:
            col_compact = masks is not None and self.backend != "dense"
        if self.spec.rewirable and masks is None:
            raise ValueError("rewirable=True requires parameter masks")
        self._freeze_static(masks=masks, col_compact=col_compact)
        self.masks = masks
        carry = self._base_carry(params, t_total)
        carry["a"] = cells.init_state(cfg, B)
        carry["gout"] = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                                     params["out"])
        carry["beta_prev"] = jnp.float32(1.0)
        self._cl = None
        rw = {"masks": masks} if self.spec.rewirable else None
        if self.backend == "dense":
            carry["M"] = SP.init_influence(cfg, B)
            carry["gw"] = jax.tree.map(
                lambda x: jnp.zeros_like(x, jnp.float32),
                cells.rec_param_tree(params))
            return self._attach_rw(carry, rw, x0, y0)
        layout = SP.flat_layout(cfg, self.spec.influence_dtype)
        self.layout = layout
        self._colm = SP.flat_col_mask(layout, masks)
        if col_compact:
            self._cl = SP.col_layout(layout, masks)
        self._segs = None
        if self.backend == "compact_fused":
            from repro.kernels import compact_fused as CF
            self._segs = CF.fused_segments(layout, self._cl)
        if rw is not None:
            if self._cl is not None:
                rw["cl"] = _cl_arrays(self._cl)
            else:
                rw["colm"] = self._colm
        P_carry = self._cl.Pc_pad if self._cl is not None else layout.P_pad
        carry["gw"] = jnp.zeros((P_carry,), jnp.float32)
        if self.backend == "pallas":
            self._jm = SP.flat_jmask(cfg, masks)
            if rw is not None:
                rw["jmask"] = self._jm
            carry["M"] = jnp.zeros((B, layout.n, P_carry), jnp.float32)
        else:
            K = SP.capacity_K(cfg.n_hidden, self.spec.capacity)
            carry["vals"] = jnp.zeros((B, K, P_carry), layout.carry_dtype)
            carry["idx"] = jnp.full((B, K), -1, jnp.int32)
        return self._attach_rw(carry, rw, x0, y0)

    @staticmethod
    def _attach_rw(carry, rw, x0, y0):
        if rw is not None:
            carry["rw"] = rw
            # last (x, y) seen: the rewire event's RigL scoring input
            carry["last"] = {"x": jnp.zeros_like(x0, dtype=jnp.float32),
                             "y": jnp.zeros_like(y0, dtype=jnp.int32)}
        return carry

    def _cl_view(self, rw):
        """The CURRENT ColLayout: static ints from init (Pc never changes),
        column maps from the carry when rewirable."""
        if self._cl is None or rw is None:
            return self._cl
        return dataclasses.replace(self._cl, **rw["cl"])

    @exact_matmuls
    def step(self, carry, x_t, y_t):
        cfg, params = self.cfg, carry["params"]
        w = cells.rec_param_tree(params)
        tt = carry["t_total"]
        rw = carry.get("rw")
        masks = rw["masks"] if rw is not None else self.masks
        cl = self._cl_view(rw)
        new = dict(carry)
        extra_stats = {}
        if self.backend == "dense":
            with jax.named_scope("partials"):
                a_new, hp, Jhat, mbar = self.cell.partials(w, carry["a"], x_t)
            with jax.named_scope("influence_update"):
                M_new = SP.influence_update(cfg, carry["M"], hp, Jhat, mbar,
                                            masks)
            with jax.named_scope("grad_readout"):
                lt, (gout_t, cbar) = jax.value_and_grad(
                    self._inst_loss, argnums=(0, 1))(params["out"], a_new,
                                                     y_t, tt)
                gw_t = SP.influence_grads(cfg, M_new, cbar)
                new["gw"] = jax.tree.map(jnp.add, carry["gw"], gw_t)
            new["M"] = M_new
            with jax.named_scope("telemetry"):
                row_density = SP._row_density(M_new)
        elif self.backend == "pallas":
            from repro.kernels import ops as kops
            colm = rw.get("colm", self._colm) if rw is not None else self._colm
            jm = rw["jmask"] if rw is not None else self._jm
            with jax.named_scope("partials"):
                a_new, hp, Jhat, mbar = self.cell.partials(w, carry["a"], x_t)
            with jax.named_scope("mbar_rows"):
                if cl is not None:
                    Mbar = SP.flat_mbar_cols(cfg, self.layout, cl, mbar)
                    kcolm = cl.live
                else:
                    Mbar = SP.flat_mbar(cfg, self.layout, mbar, colm)
                    kcolm = colm
            with jax.named_scope("influence_update"):
                M_new = kops.influence_update(hp, Jhat, carry["M"], Mbar,
                                              jmask=jm, col_mask=kcolm,
                                              interpret=self.spec.interpret)
            with jax.named_scope("grad_readout"):
                lt, (gout_t, cbar) = jax.value_and_grad(
                    self._inst_loss, argnums=(0, 1))(params["out"], a_new,
                                                     y_t, tt)
                gw_t = jnp.einsum("bk,bkp->p", cbar, M_new)
                new["gw"] = carry["gw"] + gw_t
            new["M"] = M_new
            with jax.named_scope("telemetry"):
                row_density = jnp.mean(jnp.any(M_new != 0.0, axis=2))
        else:                                   # compact / compact_fused
            from repro.kernels import compact as CK
            colm = rw.get("colm", self._colm) if rw is not None else self._colm
            if self.backend == "compact_fused":
                a_new, hp, vals_new, idx_new, count, overflow = \
                    SP.flat_compact_fused_step(
                        cfg, w, self.layout, carry["a"], carry["vals"],
                        carry["idx"], x_t, cl=cl, segments=self._segs,
                        use_kernel=True if self.spec.interpret else None,
                        interpret=self.spec.interpret)
            else:
                a_new, hp, vals_new, idx_new, count, overflow = \
                    SP.flat_compact_step(cfg, w, self.layout, carry["a"],
                                         carry["vals"], carry["idx"], x_t,
                                         colm, cl=cl)
            with jax.named_scope("grad_readout"):
                lt, (gout_t, cbar) = jax.value_and_grad(
                    self._inst_loss, argnums=(0, 1))(params["out"], a_new,
                                                     y_t, tt)
                gw_t = CK.compact_grads(vals_new, idx_new, cbar)
                new["gw"] = carry["gw"] + gw_t
            new["vals"], new["idx"] = vals_new, idx_new
            with jax.named_scope("telemetry"):
                row_density = (jnp.sum(idx_new >= 0, axis=1).mean()
                               / cfg.n_hidden)
                extra_stats["overflow"] = jnp.max(overflow)
        new["a"] = a_new
        with jax.named_scope("grad_readout"):
            new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
            new["loss"] = carry["loss"] + lt
            logits = cells.readout(params, a_new)
        if rw is not None:
            new["last"] = {"x": x_t.astype(jnp.float32),
                           "y": y_t.astype(jnp.int32)}
        with jax.named_scope("telemetry"):
            stats = {"alpha": jnp.mean(a_new == 0.0),
                     "beta": jnp.mean(hp == 0.0),
                     "beta_prev": carry["beta_prev"],
                     "m_row_density": row_density, **extra_stats}
        new["beta_prev"] = stats["beta"]
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = self._finish_gw(gw_t, cl)
            step_grads["out"] = gout_t
        out = StepOut(lt, logits, stats, step_grads)
        return new, out

    def _finish_gw(self, gw, cl=None):
        if self.backend == "dense":
            return dict(gw)
        cl = cl if cl is not None else self._cl
        if cl is not None:
            gw = SP.cols_to_flat(cl, gw)
        return SP.unflatten_flat_grads(self.cfg, self.layout, gw)

    def grads(self, carry):
        grads = self._finish_gw(carry["gw"], self._cl_view(carry.get("rw")))
        grads["out"] = carry["gout"]
        return grads

    # -- dynamic sparsity ---------------------------------------------------

    def _rigl_scores(self, carry):
        """Dense one-step gradient (straight-through surrogate) from the
        carry's current activity and last (x, y) — RigL's occasional dense
        scoring pass, computed only at rewire events."""
        if self._score_fn is None:
            cfg = self.cfg

            def loss_fn(params, a, x, y):
                w = cells.rec_param_tree(params)
                a_new = cells.step_straight_through(cfg, w, a, x)
                return cells.xent(cells.readout(params, a_new), y)

            self._score_fn = jax.jit(jax.grad(loss_fn))
        g = self._score_fn(carry["params"], carry["a"], carry["last"]["x"],
                           carry["last"]["y"])
        return cells.rec_param_tree(g)

    def rewire(self, carry, event_key, *, frac: float = 0.1,
               method: str = "rigl", block: int = 1):
        """One prune-and-regrow event with EXACT carry migration.  Host-side
        (between jitted chunks); every carry shape is preserved, so the
        compiled step keeps running — only the carry-borne column maps
        change.  Fire at update boundaries (after `reset_grads`): the
        gradient accumulator entries of pruned columns are then already
        consumed, and the surviving ones migrate like the influence."""
        from repro import sparsity as DS
        if "rw" not in carry:
            raise NotImplementedError(
                "rewire needs LearnerSpec(rewirable=True) (mask state must "
                "live in the carry)")
        cfg = self.cfg
        carry = dict(carry)
        rw = dict(carry["rw"])
        old_masks = rw["masks"]
        params = carry["params"]
        grads = self._rigl_scores(carry) if method == "rigl" else None
        new_masks = DS.rewire_masks(old_masks, cells.rec_param_tree(params),
                                    grads, frac=frac, key=event_key,
                                    method=method, block=block)
        rw["masks"] = new_masks
        # the device-side event work — old-then-new param masking (pruned
        # weights -> 0, grown weights EXACTLY 0) + the migration gather on
        # influence and gradient accumulator — runs as ONE jitted call so a
        # per-event cost is a single dispatch, amortizing under the
        # every_k-step cadence
        if self._apply_fn is None:
            def apply(params, om, nm, bufs, gather, carried):
                params = SP.apply_masks(SP.apply_masks(params, om), nm)
                bufs = {k: jnp.take(v, gather, axis=-1) * carried
                        for k, v in bufs.items()}
                return params, bufs

            def apply_dense(params, om, nm, M, gw):
                params = SP.apply_masks(SP.apply_masks(params, om), nm)
                M = DS.migrate_dense(cfg, M, nm)
                wm = {k: v for k, v in nm.items() if k != "out"}
                return params, M, SP.apply_masks(gw, wm)

            self._apply_fn = jax.jit(
                apply_dense if self.backend == "dense" else apply)
        if self.backend == "dense":
            carry["params"], carry["M"], carry["gw"] = self._apply_fn(
                params, old_masks, new_masks, carry["M"], carry["gw"])
        else:
            buf = "M" if self.backend == "pallas" else "vals"
            if self._cl is not None:
                old_cl = self._cl_view(rw)
                new_cl = SP.col_layout(self.layout, new_masks)
                gather, carried = DS.migration_plan(old_cl, new_cl)
                rw["cl"] = _cl_arrays(new_cl)
            else:
                # full-width carry: identity gather, new column mask kills
                # the pruned columns (grown ones are already exactly zero)
                colm = SP.flat_col_mask(self.layout, new_masks)
                gather = jnp.arange(colm.shape[0], dtype=jnp.int32)
                carried = colm
                rw["colm"] = colm
            carry["params"], bufs = self._apply_fn(
                params, old_masks, new_masks,
                {buf: carry[buf], "gw": carry["gw"]}, gather, carried)
            carry[buf], carry["gw"] = bufs[buf], bufs["gw"]
        if self.backend == "pallas":
            rw["jmask"] = SP.flat_jmask(cfg, new_masks)
        carry["rw"] = rw
        return carry

    def opt_mask_of(self, carry):
        masks = dict(carry["rw"]["masks"])
        masks.setdefault("out", None)
        return masks


# ---------------------------------------------------------------------------
# Exact stacked (multi-layer) RTRL
# ---------------------------------------------------------------------------

class _SingleLayerStackedLearner(_LearnerBase):
    """Stacked L=1 delegation: the single-layer engine, with params/grads
    re-wrapped into the stacked {'layers': [...], 'out': ...} structure —
    bit-for-bit the historical `delegate_single_layer` path."""

    def __init__(self, spec: LearnerSpec, scfg: StackedEGRUConfig):
        self.spec = spec
        self.cfg = scfg
        self.inner = SparseLearner(
            dataclasses.replace(spec, engine="sparse", cfg=scfg.layer_cfg(0)))

    def init(self, params, masks, batch, t_total: float = 1.0):
        sparams = dict(params["layers"][0])
        sparams["out"] = params["out"]
        # memoize the single-layer mask view: re-init with the SAME stacked
        # masks (e.g. a restarted trainer attempt) must hand the inner
        # learner the same object, or its _freeze_static identity check
        # would reject the rebuild
        if masks is None:
            self._smasks = None
        elif getattr(self, "_smasks_src", None) is not masks:
            self._smasks_src = masks
            self._smasks = dict(masks[0])
            self._smasks["out"] = None
        return self.inner.init(sparams, self._smasks, batch, t_total)

    def step(self, carry, x_t, y_t):
        carry, out = self.inner.step(carry, x_t, y_t)
        stats = dict(out.stats)
        stats["alpha_layers"] = stats["alpha"][None]
        stats["beta_layers"] = stats["beta"][None]
        grads = out.grads
        if grads is not None:
            grads = self._rewrap(grads)
        return carry, StepOut(out.loss, out.readout, stats, grads)

    @staticmethod
    def _rewrap(g):
        return {"layers": [{k: v for k, v in g.items() if k != "out"}],
                "out": g["out"]}

    def grads(self, carry):
        return self._rewrap(self.inner.grads(carry))

    def params_of(self, carry):
        return self._rewrap(carry["params"])

    def reset_grads(self, carry, params=None):
        if params is not None:                  # stacked -> single-layer view
            sparams = dict(params["layers"][0])
            sparams["out"] = params["out"]
            params = sparams
        return self.inner.reset_grads(carry, params)

    def rewire(self, carry, event_key, *, frac: float = 0.1,
               method: str = "rigl", block: int = 1):
        # layer 0 of a stacked rewire folds 0 into the event key
        # (rewire_stacked_masks convention) — keep the delegation aligned
        return self.inner.rewire(carry, jax.random.fold_in(event_key, 0),
                                 frac=frac, method=method, block=block)

    def opt_mask_of(self, carry):
        masks = self.inner.opt_mask_of(carry)
        return {"layers": [{k: v for k, v in masks.items() if k != "out"}],
                "out": None}


class StackedLearner(_LearnerBase):
    """`repro.core.stacked_rtrl` as a streaming learner: the block
    lower-triangular influence carried per layer, every backend.  Exact."""

    def __new__(cls, spec: LearnerSpec):
        scfg = cls._stacked_cfg(spec)
        if scfg.n_layers == 1 and spec.delegate_single_layer:
            return _SingleLayerStackedLearner(spec, scfg)
        self = super().__new__(cls)
        return self

    @staticmethod
    def _stacked_cfg(spec: LearnerSpec) -> StackedEGRUConfig:
        if isinstance(spec.cfg, StackedEGRUConfig):
            return spec.cfg
        return cells.stacked_config(spec.cfg, spec.layers)

    def __init__(self, spec: LearnerSpec):
        if spec.backend not in SP.BACKENDS:
            raise ValueError(
                f"backend must be one of {SP.BACKENDS}, got {spec.backend!r}")
        if spec.backend == "compact_fused" and spec.rewirable:
            raise ValueError(
                "backend='compact_fused' compiles a static gate-segment "
                "table from the ColLayout, so runtime mask rewiring is not "
                "supported — use backend='compact' with rewirable=True")
        if (SP.influence_carry_dtype(spec.influence_dtype) != jnp.float32
                and spec.backend in ("dense", "pallas")):
            raise ValueError("influence_dtype='bfloat16' needs a compact "
                             "carry (backend 'compact' or 'compact_fused')")
        self.spec = spec
        self.cfg = self._stacked_cfg(spec)
        self.backend = spec.backend
        self._score_fn = None

    def init(self, params, masks, batch, t_total: float = 1.0):
        cfg = self.cfg
        x0, y0 = batch
        B = x0.shape[0]
        L = cfg.n_layers
        col_compact = self.spec.col_compact
        if self.backend == "compact_fused":
            if col_compact is False:
                raise ValueError("compact_fused always carries the "
                                 "parameter axis column-compact")
            col_compact = True
        elif col_compact is None:
            col_compact = masks is not None and self.backend != "dense"
        if self.spec.rewirable and masks is None:
            raise ValueError("rewirable=True requires parameter masks")
        self._freeze_static(masks=masks, col_compact=col_compact)
        slayout = ST.stacked_layout(cfg)
        self.slayout = slayout
        self.lcfgs = [cfg.layer_cfg(l) for l in range(L)]
        self.lcells = [resolve_cell(c) for c in self.lcfgs]
        colm = ST.stacked_col_mask(slayout, masks)
        self.colms = ST.layer_col_masks(slayout, colm)
        self._cl = ST.stacked_col_layout(slayout, masks) if col_compact \
            else None
        self._klives = None if self._cl is None \
            else ST.layer_col_lives(slayout, self._cl)
        self._segs = None
        if self.backend == "compact_fused":
            from repro.kernels import compact_fused as CF
            self._segs = tuple(
                CF.fused_segments(slayout.layers[l], self._cl, layer=l)
                for l in range(L))
        if self.backend == "pallas":
            self._jms = tuple(
                SP.flat_jmask(self.lcfgs[l],
                              None if masks is None else masks[l])
                for l in range(L))
        rw = None
        if self.spec.rewirable:
            rw = {"masks": tuple(masks)}
            if self._cl is not None:
                rw["cl"] = _cl_arrays(self._cl)
            else:
                rw["colms"] = self.colms
            if self.backend == "pallas":
                rw["jms"] = self._jms
        P_carry = self._cl.Pc_pad if self._cl is not None else slayout.P_pad
        carry = self._base_carry(params, t_total)
        carry["a"] = cells.init_stacked_state(cfg, B)
        carry["gw"] = jnp.zeros((P_carry,), jnp.float32)
        carry["gout"] = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                                     params["out"])
        carry["beta_prev"] = jnp.ones((L,))
        if self.backend in ("dense", "pallas"):
            carry["M"] = tuple(jnp.zeros((B, n, P_carry), jnp.float32)
                               for n in cfg.layer_sizes)
        else:
            Ks = tuple(SP.capacity_K(n, self.spec.capacity)
                       for n in cfg.layer_sizes)
            cdtype = SP.influence_carry_dtype(self.spec.influence_dtype)
            carry["vals"] = tuple(jnp.zeros((B, K, P_carry), cdtype)
                                  for K in Ks)
            carry["idx"] = tuple(jnp.full((B, K), -1, jnp.int32) for K in Ks)
        return SparseLearner._attach_rw(carry, rw, x0, y0)

    def _cl_view(self, rw):
        if self._cl is None or rw is None:
            return self._cl
        return dataclasses.replace(self._cl, **rw["cl"])

    def _layer_partials(self, l, ws, a_prev, inp):
        if l == 0:
            a_new, hp, Jhat, mbar = self.lcells[l].partials(
                ws[l], a_prev, inp)
            return a_new, hp, Jhat, None, mbar
        return self.lcells[l].partials_full(ws[l], a_prev, inp)

    @exact_matmuls
    def step(self, carry, x_t, y_t):
        cfg, params = self.cfg, carry["params"]
        ws = params["layers"]
        tt = carry["t_total"]
        L = cfg.n_layers
        slayout = self.slayout
        rw = carry.get("rw")
        cl = self._cl_view(rw)
        if rw is not None:
            colms = rw.get("colms", self.colms)
            klives = None if cl is None else ST.layer_col_lives(slayout, cl)
            jms = rw.get("jms")
        else:
            colms, klives, jms = self.colms, self._klives, \
                getattr(self, "_jms", None)
        new = dict(carry)
        extra_stats = {}
        if self.backend in ("dense", "pallas"):
            inp = x_t
            a_news, hps, M_news = [], [], []
            for l in range(L):
                lay = slayout.layers[l]
                a_new, hp, Jhat, Bhat, mbar = self._layer_partials(
                    l, ws, carry["a"][l], inp)
                if cl is not None:
                    Mb = SP.flat_mbar_cols(self.lcfgs[l], lay, cl, mbar,
                                           layer=l)
                else:
                    Mb = SP.flat_mbar(self.lcfgs[l], lay, mbar, colms[l],
                                      offset=slayout.offsets[l],
                                      total_pad=slayout.P_pad)
                if l > 0:
                    Mb = Mb + jnp.einsum("bkj,bjp->bkp", Bhat, M_news[l - 1])
                if self.backend == "pallas":
                    from repro.kernels import ops as kops
                    M_new = kops.influence_update(
                        hp, Jhat, carry["M"][l], Mb, jmask=jms[l],
                        col_mask=colms[l] if cl is None else klives[l],
                        interpret=self.spec.interpret)
                else:
                    M_new = hp[:, :, None] * (
                        jnp.einsum("bkl,blp->bkp", Jhat, carry["M"][l]) + Mb)
                a_news.append(a_new)
                hps.append(hp)
                M_news.append(M_new)
                inp = a_new
            lt, (gout_t, cbar) = jax.value_and_grad(
                self._inst_loss, argnums=(0, 1))(params["out"], a_news[-1],
                                                 y_t, tt)
            gw_t = jnp.einsum("bk,bkp->p", cbar, M_news[-1])
            new["M"] = tuple(M_news)
            row_density = jnp.stack([jnp.mean(jnp.any(M != 0.0, axis=2))
                                     for M in M_news]).mean()
        else:                                   # compact
            from repro.kernels.compact import compact_grads
            a_news, hps, vals_new, idx_new, ovs = ST.stacked_compact_step(
                cfg, ws, slayout, carry["a"], carry["vals"], carry["idx"],
                x_t, colms, cl=cl, backend=self.backend, segments=self._segs,
                use_kernel=True if self.spec.interpret else None,
                interpret=self.spec.interpret)
            lt, (gout_t, cbar) = jax.value_and_grad(
                self._inst_loss, argnums=(0, 1))(params["out"], a_news[-1],
                                                 y_t, tt)
            gw_t = compact_grads(vals_new[-1], idx_new[-1], cbar)
            new["vals"], new["idx"] = vals_new, idx_new
            row_density = jnp.stack([
                jnp.sum(i >= 0, axis=1).mean() / n
                for i, n in zip(idx_new, cfg.layer_sizes)]).mean()
            extra_stats["overflow"] = jnp.max(ovs)
        new["a"] = tuple(a_news)
        new["gw"] = carry["gw"] + gw_t
        new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
        new["loss"] = carry["loss"] + lt
        if rw is not None:
            new["last"] = {"x": x_t.astype(jnp.float32),
                           "y": y_t.astype(jnp.int32)}
        alpha_l = jnp.stack([jnp.mean(a == 0.0) for a in a_news])
        beta_l = jnp.stack([jnp.mean(h == 0.0) for h in hps])
        stats = {"alpha": alpha_l.mean(), "beta": beta_l.mean(),
                 "alpha_layers": alpha_l, "beta_layers": beta_l,
                 "beta_prev": carry["beta_prev"],
                 "m_row_density": row_density, **extra_stats}
        new["beta_prev"] = beta_l
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = self._finish_gw(gw_t, cl)
            step_grads["out"] = gout_t
        out = StepOut(lt, cells.readout(params, a_news[-1]), stats,
                      step_grads)
        return new, out

    def _finish_gw(self, gw, cl=None):
        cl = cl if cl is not None else self._cl
        if cl is not None:
            gw = SP.cols_to_flat(cl, gw)
        return ST.unflatten_stacked_grads(self.cfg, self.slayout, gw)

    def grads(self, carry):
        grads = self._finish_gw(carry["gw"], self._cl_view(carry.get("rw")))
        grads["out"] = carry["gout"]
        return grads

    # -- dynamic sparsity ---------------------------------------------------

    def _rigl_scores(self, carry):
        if self._score_fn is None:
            cfg = self.cfg

            def loss_fn(params, a_prevs, x, y):
                a_new = cells.stacked_step_straight_through(
                    cfg, params["layers"], a_prevs, x)
                return cells.xent(cells.readout(params, a_new[-1]), y)

            self._score_fn = jax.jit(jax.grad(loss_fn))
        g = self._score_fn(carry["params"], carry["a"], carry["last"]["x"],
                           carry["last"]["y"])
        return g["layers"]

    def rewire(self, carry, event_key, *, frac: float = 0.1,
               method: str = "rigl", block: int = 1):
        """Stacked prune-and-regrow event: per-layer criteria on the shared
        concatenated column axis; ONE migration plan remaps every layer's
        buffer (they share the stacked ColLayout).  See
        SparseLearner.rewire for the exactness contract."""
        from repro import sparsity as DS
        if "rw" not in carry:
            raise NotImplementedError(
                "rewire needs LearnerSpec(rewirable=True) (mask state must "
                "live in the carry)")
        carry = dict(carry)
        rw = dict(carry["rw"])
        old_masks = list(rw["masks"])
        params = dict(carry["params"])
        grads = self._rigl_scores(carry) if method == "rigl" else None
        new_masks = DS.rewire_stacked_masks(
            old_masks, params["layers"], grads, frac=frac, key=event_key,
            method=method, block=block)
        params["layers"] = [
            SP.apply_masks(SP.apply_masks(p, om), nm)
            for p, om, nm in zip(params["layers"], old_masks, new_masks)]
        carry["params"] = params
        rw["masks"] = tuple(new_masks)
        buf = "M" if self.backend in ("dense", "pallas") else "vals"
        if self._cl is not None:
            old_cl = self._cl_view(rw)
            new_cl = ST.stacked_col_layout(self.slayout, new_masks)
            plan = DS.migration_plan(old_cl, new_cl)
            carry[buf] = tuple(
                DS.migrate_influence(old_cl, new_cl, M, plan=plan)
                for M in carry[buf])
            carry["gw"] = DS.migrate_influence(old_cl, new_cl, carry["gw"],
                                               plan=plan)
            rw["cl"] = _cl_arrays(new_cl)
        else:
            colm = ST.stacked_col_mask(self.slayout, new_masks)
            colms = ST.layer_col_masks(self.slayout, colm)
            carry[buf] = tuple(DS.migrate_flat(cm, M)
                               for cm, M in zip(colms, carry[buf]))
            carry["gw"] = DS.migrate_flat(colm, carry["gw"])
            rw["colms"] = colms
        if self.backend == "pallas":
            rw["jms"] = tuple(SP.flat_jmask(self.lcfgs[l], new_masks[l])
                              for l in range(self.cfg.n_layers))
        carry["rw"] = rw
        return carry

    def opt_mask_of(self, carry):
        return {"layers": list(carry["rw"]["masks"]), "out": None}


# ---------------------------------------------------------------------------
# Scaled / sharded compact RTRL
# ---------------------------------------------------------------------------

class ScaledLearner(_LearnerBase):
    """`repro.core.scaled_rtrl` as a streaming learner: the row-compact
    (optionally dual-compact) carry at LM scale, single layer or stacked.
    Exact up to row-capacity overflow (reported per step)."""

    def __init__(self, spec: LearnerSpec):
        # historical scaled specs carry the LearnerSpec default
        # backend="dense"; the scaled engine is compact by construction, so
        # only "compact_fused" changes the step — everything else is the
        # legacy compact path
        self.fused = spec.backend == "compact_fused"
        if self.fused and spec.rewirable:
            raise ValueError(
                "backend='compact_fused' compiles a static gate-segment "
                "table from the ColLayout, so runtime mask rewiring is not "
                "supported — use backend='compact' with rewirable=True")
        SP.influence_carry_dtype(spec.influence_dtype)   # validate early
        self.spec = spec
        self.cfg = spec.cfg                 # ScaledRTRLConfig
        self.stacked = self.cfg.n_layers > 1
        self._score_fn = None

    def init(self, params, masks, batch, t_total: float = 1.0):
        from repro.core import scaled_rtrl as SC
        cfg = self.cfg
        x0, y0 = batch
        col_compact = self.spec.col_compact
        if self.fused:
            if col_compact is False:
                raise ValueError("compact_fused always carries the "
                                 "parameter axis column-compact")
            col_compact = True
        elif col_compact is None:
            col_compact = masks is not None
        if self.spec.rewirable and not (masks is not None and col_compact):
            raise ValueError(
                "rewirable ScaledLearner requires masks and col_compact "
                "(the full-width scaled carry tracks dead columns, so "
                "grow-at-zero exactness only holds on the compact carry)")
        self._freeze_static(masks=masks, col_compact=col_compact)
        self._cl = cfg.col_layout(masks) if col_compact else None
        self._segs = None
        if self.fused:
            from repro.kernels import compact_fused as CF
            if self.stacked:
                slayout = cfg.slayout()
                self._segs = tuple(
                    CF.fused_segments(slayout.layers[l], self._cl, layer=l)
                    for l in range(cfg.n_layers))
            else:
                self._segs = CF.fused_segments(cfg.layout(), self._cl)
        if self._cl is not None:
            P_carry = self._cl.Pc_pad
        else:
            P_carry = (cfg.slayout().P_pad if self.stacked
                       else cfg.layout().P_pad)
        carry = self._base_carry(params, t_total)
        carry["state"] = SC.init_state(cfg, self._cl,
                                       self.spec.influence_dtype)
        carry["gw"] = jnp.zeros((P_carry,), jnp.float32)
        carry["gout"] = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                                     params["out"])
        rw = None
        if self.spec.rewirable:
            rw = {"masks": tuple(masks) if self.stacked else masks,
                  "cl": _cl_arrays(self._cl)}
        return SparseLearner._attach_rw(carry, rw, x0, y0)

    def _cl_view(self, rw):
        if self._cl is None or rw is None:
            return self._cl
        return dataclasses.replace(self._cl, **rw["cl"])

    @exact_matmuls
    def step(self, carry, x_t, y_t):
        from repro.core import scaled_rtrl as SC
        from repro.kernels.compact import compact_grads
        cfg, params = self.cfg, carry["params"]
        w = params["layers"] if self.stacked else cells.rec_param_tree(params)
        tt = carry["t_total"]
        rw = carry.get("rw")
        cl = self._cl_view(rw)
        state, overflow = SC.compact_step(
            cfg, w, carry["state"], x_t, cl=cl,
            backend="compact_fused" if self.fused else "compact",
            segments=self._segs,
            use_kernel=True if self.spec.interpret else None,
            interpret=self.spec.interpret)
        a_top = state["a"][-1] if self.stacked else state["a"]
        lt, (gout_t, cbar) = jax.value_and_grad(
            self._inst_loss, argnums=(0, 1))(params["out"], a_top, y_t, tt)
        if self.stacked:
            gw_t = compact_grads(state["vals"][-1], state["idx"][-1], cbar)
        else:
            gw_t = compact_grads(state["vals"], state["idx"], cbar)
        new = dict(carry)
        new["state"] = state
        new["gw"] = carry["gw"] + gw_t
        new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
        new["loss"] = carry["loss"] + lt
        if rw is not None:
            new["last"] = {"x": x_t.astype(jnp.float32),
                           "y": y_t.astype(jnp.int32)}
        stats = {"overflow": overflow if self.stacked
                 else jnp.max(overflow)}
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = self._finish_gw(gw_t, cl)
            step_grads["out"] = gout_t
        return new, StepOut(lt, cells.readout(params, a_top), stats,
                            step_grads)

    def _finish_gw(self, gw, cl=None):
        cfg = self.cfg
        cl = cl if cl is not None else self._cl
        if cl is not None:
            gw = SP.cols_to_flat(cl, gw)
        if self.stacked:
            return ST.unflatten_stacked_grads(cfg.stacked_cfg(),
                                              cfg.slayout(), gw)
        return SP.unflatten_flat_grads(cfg.cell_cfg(), cfg.layout(), gw)

    def grads(self, carry):
        grads = self._finish_gw(carry["gw"], self._cl_view(carry.get("rw")))
        grads["out"] = carry["gout"]
        return grads

    # -- dynamic sparsity ---------------------------------------------------

    def _rigl_scores(self, carry):
        cfg = self.cfg
        if self._score_fn is None:
            if self.stacked:
                scfg = cfg.stacked_cfg()

                def loss_fn(params, a, x, y):
                    a_new = cells.stacked_step_straight_through(
                        scfg, params["layers"], a, x)
                    return cells.xent(cells.readout(params, a_new[-1]), y)
            else:
                ccfg = cfg.cell_cfg()

                def loss_fn(params, a, x, y):
                    w = cells.rec_param_tree(params)
                    a_new = cells.step_straight_through(ccfg, w, a, x)
                    return cells.xent(cells.readout(params, a_new), y)

            self._score_fn = jax.jit(jax.grad(loss_fn))
        g = self._score_fn(carry["params"], carry["state"]["a"],
                           carry["last"]["x"], carry["last"]["y"])
        return g["layers"] if self.stacked else cells.rec_param_tree(g)

    def rewire(self, carry, event_key, *, frac: float = 0.1,
               method: str = "rigl", block: int = 1):
        """Scaled (optionally stacked/sharded) prune-and-regrow event on
        the dual-compact carry.  The once-per-event migration gather may
        move surviving columns across model shards; the steady-state step
        keeps its zero-collective influence update unchanged."""
        from repro import sparsity as DS
        if "rw" not in carry:
            raise NotImplementedError(
                "rewire needs LearnerSpec(rewirable=True) (mask state must "
                "live in the carry)")
        cfg = self.cfg
        carry = dict(carry)
        rw = dict(carry["rw"])
        grads = self._rigl_scores(carry) if method == "rigl" else None
        params = dict(carry["params"])
        if self.stacked:
            old_masks = list(rw["masks"])
            new_masks = DS.rewire_stacked_masks(
                old_masks, params["layers"], grads, frac=frac, key=event_key,
                method=method, block=block)
            params["layers"] = [
                SP.apply_masks(SP.apply_masks(p, om), nm)
                for p, om, nm in zip(params["layers"], old_masks, new_masks)]
            rw["masks"] = tuple(new_masks)
        else:
            old_masks = rw["masks"]
            new_masks = DS.rewire_masks(
                old_masks, cells.rec_param_tree(params), grads, frac=frac,
                key=event_key, method=method, block=block)
            params = SP.apply_masks(SP.apply_masks(params, old_masks),
                                    new_masks)
            rw["masks"] = new_masks
        carry["params"] = params
        old_cl = self._cl_view(rw)
        new_cl = cfg.col_layout(new_masks)
        plan = DS.migration_plan(old_cl, new_cl)
        state = dict(carry["state"])
        if self.stacked:
            state["vals"] = tuple(
                DS.migrate_influence(old_cl, new_cl, v, plan=plan)
                for v in state["vals"])
        else:
            state["vals"] = DS.migrate_influence(old_cl, new_cl,
                                                 state["vals"], plan=plan)
        carry["state"] = state
        carry["gw"] = DS.migrate_influence(old_cl, new_cl, carry["gw"],
                                           plan=plan)
        rw["cl"] = _cl_arrays(new_cl)
        carry["rw"] = rw
        return carry

    def opt_mask_of(self, carry):
        masks = carry["rw"]["masks"]
        if self.stacked:
            return {"layers": list(masks), "out": None}
        masks = dict(masks)
        masks.setdefault("out", None)
        return masks


# ---------------------------------------------------------------------------
# Diagonal-recurrence eligibility traces (exact, O(n·p) per step)
# ---------------------------------------------------------------------------

class DiagExactLearner(_LearnerBase):
    """Exact eligibility-trace RTRL for zoo cells whose state Jacobian is
    diagonal, per unit or per head:

      jac_kind "diagonal" (RG-LRU via `repro.cells.rglru`, the diag_rtrl toy
        cell, the RWKV decay family): J_t = diag(a_t), a_t [B, n], factors
        the influence matrix into independent per-parameter traces

            e_t[w] = a_t * e_{t-1}[w] + mbar_t[w]

        so one step costs O(n·p) FLOPs and O(p) trace memory — no [B, K, P]
        influence buffer and no n² Jacobian factor.
      jac_kind "head_diagonal" (the Mamba-2 mixer of `repro.cells.mamba2`):
        a state [B, H, P, N] whose decay is one scalar per head, traces
        [B, H, P, N, ...] with rank-1 increments; the cell gives the step's
        terms and the one-pass trace update, and its learned leaves also
        reach the loss without the state (immediate terms, added to the
        traces' gradient).  The layers around the learned one run forward
        only: their weights (`init(..., frozen=...)`) ride in the carry.

    Either way a step is the cell's two halves: `cell.exact_terms` (new
    state, decay, increments, lam = dL_t/dh_t, immediate gradients, loss,
    logits, stats; `cells.rglru.DiagonalExact` for the diagonal kind), then
    `cell.trace_update`, which also contracts lam against the new traces.
    `engine="diag_exact"` is the cell-agnostic spelling; `engine="diag"`
    keeps the historical name (same carry layout for DiagCellConfig
    specs).  With parameter masks the
    trace increments of dead parameters are zeroed every step, so their
    traces and gradients stay exactly 0."""

    KINDS = ("diagonal", "head_diagonal")

    def __init__(self, spec: LearnerSpec):
        self.spec = spec
        self.cfg = spec.cfg
        self.cell = resolve_cell(spec.cfg)
        if self.cell.jac_kind not in self.KINDS:
            raise ValueError(
                f"engine='diag_exact' needs a diagonal-Jacobian cell; "
                f"{self.cell.name!r} has jac_kind={self.cell.jac_kind!r}")

    def init(self, params, masks, batch, t_total: float = 1.0,
             frozen=None):
        x0, _ = batch
        B = x0.shape[0]
        self._freeze_static(masks=masks)
        self.masks = masks
        carry = self._base_carry(params, t_total)
        carry["h"] = self.cell.init_state(B)
        carry["tr"] = self.cell.init_traces(B)
        carry["gw"] = jax.tree.map(jnp.zeros_like,
                                   self.cell.rec_params(params))
        carry["gout"] = jax.tree.map(jnp.zeros_like, params["out"])
        if frozen is not None:
            carry["frozen"] = frozen
        return carry

    @exact_matmuls
    def step(self, carry, x_t, y_t):
        params = carry["params"]
        h_new, decay, mbar, lam, g_imm, gout_t, lt, logits, stats = (
            self.cell.exact_terms(params, carry.get("frozen"), carry["h"],
                                  x_t, y_t, carry["t_total"], self.masks))
        tr_new, gw_t = self.cell.trace_update(carry["tr"], decay, mbar, lam)
        if g_imm is not None:
            gw_t = jax.tree.map(jnp.add, gw_t, g_imm)
        new = dict(carry)
        new["h"], new["tr"] = h_new, tr_new
        new["gw"] = jax.tree.map(jnp.add, carry["gw"], gw_t)
        new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
        new["loss"] = carry["loss"] + lt
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = dict(gw_t)
            step_grads["out"] = gout_t
        return new, StepOut(lt, logits, stats, step_grads)

    def grads(self, carry):
        grads = dict(carry["gw"])
        grads["out"] = carry["gout"]
        return grads


# keep the historical class name importable
DiagLearner = DiagExactLearner


# ---------------------------------------------------------------------------
# e-prop for spiking cells (approximate, O(n·p) per step)
# ---------------------------------------------------------------------------

class EpropLearner(_LearnerBase):
    """Bellec-style e-prop for cells exposing `eprop_step` (the SNN in
    `repro.cells.snn`): rank-1 membrane traces plus full adaptation traces,
    with the learning signal broadcast exactly from the readout (symmetric
    e-prop).  An APPROXIMATION — the explicit spike recurrence through R is
    dropped; alignment vs the surrogate-gradient BPTT oracle is measured in
    tests/test_cells.py."""

    def __init__(self, spec: LearnerSpec):
        self.spec = spec
        self.cfg = spec.cfg
        self.cell = resolve_cell(spec.cfg)
        if not hasattr(self.cell, "eprop_step"):
            raise ValueError(
                f"engine='eprop' needs a cell exposing eprop_step; "
                f"{self.cell.name!r} does not")

    def init(self, params, masks, batch, t_total: float = 1.0):
        x0, _ = batch
        B = x0.shape[0]
        self._freeze_static(masks=masks)
        self.masks = masks
        carry = self._base_carry(params, t_total)
        carry["h"] = self.cell.init_state(B)
        carry["tr"] = self.cell.init_traces(B)
        carry["gw"] = jax.tree.map(jnp.zeros_like,
                                   self.cell.rec_params(params))
        carry["gout"] = jax.tree.map(jnp.zeros_like, params["out"])
        return carry

    def step(self, carry, x_t, y_t):
        params = carry["params"]
        w = self.cell.rec_params(params)
        tt = carry["t_total"]
        state_new, tr_new, e = self.cell.eprop_step(w, carry["h"],
                                                    carry["tr"], x_t)
        if self.masks is not None:
            e = jax.tree.map(lambda el, mk: el * mk, e, self.masks)
        z_new = state_new["z"]

        def inst_loss(po, zi):
            logits = self.cell.readout({"out": po}, zi)
            lab = jnp.maximum(y_t, 0)
            ls = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(ls, lab[:, None], 1)) / tt

        lt, (gout_t, cbar) = jax.value_and_grad(inst_loss, argnums=(0, 1))(
            params["out"], z_new)
        gw_t = jax.tree.map(
            lambda el: jnp.einsum("bk,b...k->...k", cbar, el), e)
        new = dict(carry)
        new["h"], new["tr"] = state_new, tr_new
        new["gw"] = jax.tree.map(jnp.add, carry["gw"], gw_t)
        new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
        new["loss"] = carry["loss"] + lt
        stats = {"alpha": jnp.mean(z_new != 0.0)}
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = dict(gw_t)
            step_grads["out"] = gout_t
        return new, StepOut(lt, self.cell.readout(params, z_new), stats,
                            step_grads)

    def grads(self, carry):
        grads = dict(carry["gw"])
        grads["out"] = carry["gout"]
        return grads


# ---------------------------------------------------------------------------
# SnAp-1 / SnAp-2 approximations
# ---------------------------------------------------------------------------

class SnapLearner(_LearnerBase):
    """`repro.core.snap` as a streaming learner: the influence pruned to the
    SnAp-n pattern each step (an APPROXIMATION — the Table-1 baseline the
    exact engines are measured against)."""

    def __init__(self, spec: LearnerSpec):
        self.spec = spec
        self.cfg: EGRUConfig = spec.cfg
        self.cell = resolve_cell(spec.cfg)
        self.order = spec.order

    def init(self, params, masks, batch, t_total: float = 1.0):
        from repro.core import snap as SN
        cfg = self.cfg
        x0, _ = batch
        B = x0.shape[0]
        self._freeze_static(masks=masks)
        self.masks = masks
        if self.order == 1:
            self.keep = jnp.eye(cfg.n_hidden)
        else:
            self.keep = SN.snap2_pattern(cfg, masks)
        carry = self._base_carry(params, t_total)
        carry["a"] = cells.init_state(cfg, B)
        carry["M"] = SP.init_influence(cfg, B)
        carry["gw"] = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                                   cells.rec_param_tree(params))
        carry["gout"] = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                                     params["out"])
        return carry

    def _prune(self, M):
        keep = self.keep
        return {g: Mg * (keep[None, :, :, None] if Mg.ndim == 4
                         else keep[None]) for g, Mg in M.items()}

    def step(self, carry, x_t, y_t):
        cfg, params = self.cfg, carry["params"]
        w = cells.rec_param_tree(params)
        tt = carry["t_total"]
        a_new, hp, Jhat, mbar = self.cell.partials(w, carry["a"], x_t)
        M_new = self._prune(SP.influence_update(cfg, carry["M"], hp, Jhat,
                                                mbar, self.masks))
        lt, (gout_t, cbar) = jax.value_and_grad(
            self._inst_loss, argnums=(0, 1))(params["out"], a_new, y_t, tt)
        gw_t = SP.influence_grads(cfg, M_new, cbar)
        new = dict(carry)
        new["a"], new["M"] = a_new, M_new
        new["gw"] = jax.tree.map(jnp.add, carry["gw"], gw_t)
        new["gout"] = jax.tree.map(jnp.add, carry["gout"], gout_t)
        new["loss"] = carry["loss"] + lt
        stats = {"beta": jnp.mean(hp == 0.0)}
        step_grads = None
        if self.spec.per_step_grads:
            step_grads = dict(gw_t)
            step_grads["out"] = gout_t
        return new, StepOut(lt, cells.readout(params, a_new), stats,
                            step_grads)

    def grads(self, carry):
        grads = dict(carry["gw"])
        grads["out"] = carry["gout"]
        return grads


# ---------------------------------------------------------------------------
# BPTT sequence-adapter oracle
# ---------------------------------------------------------------------------

class BPTTLearner(_LearnerBase):
    """BPTT behind the streaming protocol — the oracle that shows what RTRL
    buys.  Buffers the last `horizon` inputs ([H, B, n_in] + labels) in the
    carry; `grads` re-runs the window forward and reverse-differentiates it
    (memory O(H), NOT O(1) — the limitation the paper removes).

    `reset_grads` restarts the window at the current activity (truncated
    BPTT): with an update every k <= horizon steps this is exactly TBPTT-k.
    Steps beyond the horizon overwrite the last slot and set the
    'bptt_overflow' stat — size the horizon to the update window."""

    def __init__(self, spec: LearnerSpec):
        self.spec = spec
        self.cfg: EGRUConfig = spec.cfg

    def init(self, params, masks, batch, t_total: float = 1.0):
        cfg = self.cfg
        x0, y0 = batch
        B = x0.shape[0]
        H = self.spec.horizon
        if H is None:
            H = max(1, int(round(float(t_total))))
        self._freeze_static(horizon=H)
        self.horizon = H
        carry = self._base_carry(params, t_total)
        carry["a"] = cells.init_state(cfg, B)
        carry["a0"] = cells.init_state(cfg, B)
        carry["xbuf"] = jnp.zeros((H,) + x0.shape, jnp.float32)
        carry["ybuf"] = jnp.zeros((H,) + y0.shape, jnp.int32)
        carry["pos"] = jnp.int32(0)
        return carry

    def step(self, carry, x_t, y_t):
        cfg, params = self.cfg, carry["params"]
        w = cells.rec_param_tree(params)
        tt = carry["t_total"]
        a_new = cells.step_straight_through(cfg, w, carry["a"], x_t)
        lt = cells.xent(cells.readout(params, a_new), y_t) / tt
        slot = jnp.minimum(carry["pos"], self.horizon - 1)
        new = dict(carry)
        new["a"] = a_new
        new["xbuf"] = jax.lax.dynamic_update_index_in_dim(
            carry["xbuf"], x_t.astype(jnp.float32), slot, 0)
        new["ybuf"] = jax.lax.dynamic_update_index_in_dim(
            carry["ybuf"], y_t.astype(jnp.int32), slot, 0)
        new["pos"] = carry["pos"] + 1
        new["loss"] = carry["loss"] + lt
        stats = {"alpha": jnp.mean(a_new == 0.0),
                 "bptt_overflow": (carry["pos"] >= self.horizon)
                 .astype(jnp.int32)}
        return new, StepOut(lt, cells.readout(params, a_new), stats, None)

    def grads(self, carry):
        cfg = self.cfg
        H = self.horizon
        xbuf, ybuf = carry["xbuf"], carry["ybuf"]
        a0, pos, tt = carry["a0"], carry["pos"], carry["t_total"]

        def loss_fn(params):
            w = cells.rec_param_tree(params)

            def body(a, x_t):
                a_new = cells.step_straight_through(cfg, w, a, x_t)
                return a_new, cells.readout(params, a_new)

            _, logits_t = jax.lax.scan(body, a0, xbuf)
            losses = jax.vmap(cells.xent)(logits_t, ybuf)
            wmask = (jnp.arange(H) < pos).astype(losses.dtype)
            return jnp.sum(losses * wmask) / tt

        return jax.grad(loss_fn)(carry["params"])

    def reset_grads(self, carry, params=None):
        carry = super().reset_grads(carry, params)
        carry["a0"] = carry["a"]
        carry["pos"] = jnp.zeros_like(carry["pos"])
        return carry


# ---------------------------------------------------------------------------
# Registry + whole-sequence scan wrapper
# ---------------------------------------------------------------------------

ENGINES = {
    "sparse": SparseLearner,
    "stacked": StackedLearner,
    "scaled": ScaledLearner,
    "diag": DiagExactLearner,        # historical name, same engine
    "diag_exact": DiagExactLearner,
    "eprop": EpropLearner,
    "snap": SnapLearner,
    "bptt": BPTTLearner,
}


def make_learner(spec: LearnerSpec) -> Learner:
    """Construct the learner named by `spec.engine` — the single entry point
    the legacy wrappers, the online trainer, and future serving/sharding
    layers all configure engines through."""
    if spec.engine not in ENGINES:
        raise ValueError(
            f"engine must be one of {tuple(ENGINES)}, got {spec.engine!r}")
    if spec.cfg is None:
        raise ValueError("LearnerSpec.cfg is required")
    return ENGINES[spec.engine](spec)


def scan_learner(learner: Learner, params: Tree, masks: Tree | None,
                 xs: jax.Array, labels: jax.Array):
    """Whole-sequence driver: scan the learner over xs [T, B, ...] with a
    fixed label, normalizing the per-step loss by T.  This IS the legacy
    `*_loss_and_grads` semantics — those functions are this wrapper."""
    T = xs.shape[0]
    carry0 = learner.init(params, masks, (xs[0], labels), t_total=T)

    def body(carry, x_t):
        carry, out = learner.step(carry, x_t, labels)
        return carry, out.stats

    carry, stats = jax.lax.scan(body, carry0, xs)
    return carry["loss"], learner.grads(carry), stats
