"""The cell zoo: every recurrent architecture behind ONE pluggable protocol.

A *cell* packages everything a gradient engine needs to know about a
recurrent architecture, so `repro.core.learner`'s engines are cell-agnostic:

    cell.name            short id ("egru" | "rglru" | "snn" | "diag")
    cell.jac_kind        "dense"    -> partials yields J-hat [B, n, n]
                         "diagonal" -> partials yields the diagonal [B, n]
                         "head_diagonal" -> one decay per head of a
                                    [B, H, P, N] state (no partials)
    cell.cfg             the config dataclass the cell was built from
    cell.init_params(key)            full parameter tree (incl. readout)
    cell.rec_params(params)          the recurrent subset w
    cell.init_state(batch)           recurrent state (array or dict)
    cell.partials(w, state, x_t)  -> (state', hp, Jhat_or_diag, mbar)
    cell.step_st(w, state, x_t)      autodiff-able forward (shared surrogate
                                     gradient) — BPTT oracles / RigL scoring
    cell.readout(params, state)   -> logits [B, n_out]
    cell.activity_mask(state)     -> bool [B, n] active units (alpha stat)

What `mbar` means depends on jac_kind: for dense cells it is the EGRU
per-gate Mbar-group dict the flat influence layout consumes; for diagonal
cells it is a pytree of per-parameter trace increments (trailing axis n),
and cells additionally expose `init_traces(batch)` so `engine="diag_exact"`
can carry exact O(n·p) eligibility traces.  Cells of both diagonal kinds
give the two halves of a `diag_exact` step, `exact_terms` and
`trace_update` (`rglru.DiagonalExact` for jac_kind "diagonal").  The SNN cell instead exposes
`eprop_step` for the approximate `engine="eprop"` recursion (see
repro.cells.snn).

`resolve_cell` maps a config object (what LearnerSpec.cfg already carries)
to its cell, so existing specs keep working unchanged.
"""
from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax

from repro.cells.egru import EGRUCell
from repro.cells.mamba2 import GraniteHybridConfig, Mamba2PeriodCell
from repro.cells.rglru import DiagCell, RGLRUCell, RGLRUCellConfig
from repro.cells.snn import SNNCell, SNNConfig

Tree = Any


@runtime_checkable
class Cell(Protocol):
    """Structural protocol every zoo cell satisfies (see module docstring
    for the full contract)."""
    name: str
    jac_kind: str
    cfg: Any

    def init_params(self, key: jax.Array) -> Tree: ...

    def rec_params(self, params: Tree) -> Tree: ...

    def init_state(self, batch: int) -> Any: ...

    def partials(self, w: Tree, state: Any, x_t: jax.Array) -> tuple: ...

    def step_st(self, w: Tree, state: Any, x_t: jax.Array) -> Any: ...

    def readout(self, params: Tree, state: Any) -> jax.Array: ...

    def activity_mask(self, state: Any) -> jax.Array: ...


CELLS = {
    "egru": EGRUCell,
    "rglru": RGLRUCell,
    "snn": SNNCell,
    "diag": DiagCell,
    "mamba2": Mamba2PeriodCell,
}


def make_cell(name: str, cfg: Any) -> Cell:
    """Construct the cell named `name` around `cfg`."""
    if name not in CELLS:
        raise ValueError(f"cell must be one of {tuple(CELLS)}, got {name!r}")
    return CELLS[name](cfg)


def resolve_cell(cfg: Any) -> Cell:
    """Map a LearnerSpec.cfg object to its zoo cell by config type — the
    dispatch rule that lets every engine stay cell-agnostic while existing
    specs (EGRUConfig, DiagCellConfig, ...) keep working unchanged."""
    from repro.core.cells import EGRUConfig
    from repro.core.diag_rtrl import DiagCellConfig
    if isinstance(cfg, EGRUConfig):
        return EGRUCell(cfg)
    if isinstance(cfg, RGLRUCellConfig):
        return RGLRUCell(cfg)
    if isinstance(cfg, SNNConfig):
        return SNNCell(cfg)
    if isinstance(cfg, DiagCellConfig):
        return DiagCell(cfg)
    if isinstance(cfg, GraniteHybridConfig):
        return Mamba2PeriodCell(cfg)
    raise ValueError(
        f"no cell registered for config type {type(cfg).__name__!r}; "
        f"known cells: {tuple(CELLS)}")
