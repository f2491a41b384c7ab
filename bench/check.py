"""How `correct` is decided: what the timed path produced against the plain
reference, run in float64 on the host's CPU.

The reference is found by name, from the configuration's `model.cell`:
`bench/references/<cell>.py`.  A cell of a new architecture brings its own
module there; nothing here knows a cell's equations.  The module exposes

  INPUTS          the names of a stream's input arrays in the record
  make_reference(model, windows, matmul="highest", **fault) -> run
                  `run(shared, stream)` for one stream: shared is
                  {"params0", "masks"} (canonical leaves), stream holds the
                  INPUTS and optionally "start" (the state at the first
                  step, in the module's own layout) and "alt" (one of the
                  module's alternatives); it returns {"loss" [windows],
                  "grad1" {leaf}, "params" {leaf}, ...}.  `matmul` is
                  "highest" or "bf16x3" (the control's precision); a fault
                  keyword such as `drop_half_batch` plants a fault.
  alternatives(out) -> [alt, ...]   optional: other exact outcomes of one
                  stream, from its run's host outputs (near-ties), against
                  which the stream is judged too

A cell's record (`Cell.check_record()`) is a list of parts.  A part is
{"part", "model", "params0", "masks", "windows", "streams"}; each stream
holds its inputs, the window losses the program read back and, where the
program's state could be read at the window boundaries, the first gradient
as the optimizer got it (from AdamW's first moment after one update) and
the parameters after the last checked window.  A stream with a "start" is
compared from that state: its parameters' change is taken from the start's
parameters.  Three numbers are compared in each part, each the worst over
its streams, and named `<part>.<number>` (the number alone for the part
named ""):

  loss_gap    max over windows of |loss - ref| / |ref|
  grad1_gap   max over leaves of | |g| - |g_ref| | / max(|g_ref|, median)
  change_gap_median  the median over leaves of the same gap for the
              parameters' change over the checked windows

where |.| is a leaf's norm and `median` the median leaf norm of the
reference.  Leaves whose reference gradient is under a thousandth of the
median leaf's are left out of both: they move by round-off alone.

The change is compared at its median leaf, not its worst: an element whose
gradient is zero to rounding in a later window takes an AdamW step of up to
the learning rate from round-off alone, so the worst leaf's change swings
from seed to seed in sound float32 runs.  The worst leaf's reading is
reported beside the check (`change_gap_worst_leaf`), not compared.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

NUMBERS = ("loss_gap", "grad1_gap", "change_gap_median")
REFERENCES = Path(__file__).resolve().parent / "references"


def reference_path(cell: str) -> Path:
    return REFERENCES / f"{cell}.py"


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: str):
    """The reference of a configuration's cell; exits with code 2 where
    there is none."""
    path = reference_path(cell)
    if not path.is_file():
        print(f"bench: missing reference {path.name} for cell {cell!r} "
              f"(bench/references/)", file=sys.stderr)
        sys.exit(2)
    return _load(path)


def _norms(tree: dict, names) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(tree[k], np.float64))
                     for k in names])


def leaf_gaps(prog: dict, ref: dict, names) -> np.ndarray:
    """| |prog| - |ref| | / max(|ref|, median |ref|) for each of `names`."""
    p, r = _norms(prog, names), _norms(ref, names)
    return np.abs(p - r) / np.maximum(r, np.median(r))


def moving_leaves(grad_ref: dict) -> list:
    """Leaves whose reference gradient is nonzero and at least a thousandth
    of the median leaf's."""
    names = sorted(grad_ref)
    g = _norms(grad_ref, names)
    med = np.median(g)
    return [k for k, v in zip(names, g) if v > 0 and v >= 1e-3 * med]


def stream_numbers(s: dict, ref: dict, params0: dict) -> dict:
    """The compared numbers of one stream against one reference outcome."""
    lp = np.asarray(s["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)[:lp.size]
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))
           if lp.size else 0.0}
    names = moving_leaves(ref["grad1"])
    if s.get("grad1") is not None:
        out["grad1_gap"] = float(np.max(
            leaf_gaps(s["grad1"], ref["grad1"], names)))
    if s.get("params") is not None:
        if s.get("start") is not None:
            params0 = s["start"]["params"]
        dp = {k: s["params"][k] - params0[k] for k in names}
        dr = {k: ref["params"][k] - params0[k] for k in names}
        gaps = leaf_gaps(dp, dr, names)
        out["change_gap_median"] = float(np.median(gaps))
        out["change_gap_worst_leaf"] = float(np.max(gaps))
    return out


def _score(nums: dict, limits: dict) -> float:
    return max(v / limits[k] for k, v in nums.items() if k in limits)


@functools.lru_cache(maxsize=16)
def _compiled(cell: str, model_json: str, windows: int, matmul: str,
              fault: tuple):
    """The jitted reference, vmapped over streams (one per set of settings,
    so near-tie reruns reuse its compile)."""
    import jax

    ref = reference_module(cell)
    run = ref.make_reference(json.loads(model_json), windows, matmul=matmul,
                             **dict(fault))
    return jax.jit(jax.vmap(run, in_axes=(None, 0)))


def _as(x, dtype):
    """Floating arrays in `dtype`, integers as int32, booleans as they
    are."""
    a = np.asarray(x)
    if np.issubdtype(a.dtype, np.floating):
        return a.astype(dtype)
    if np.issubdtype(a.dtype, np.integer):
        return a.astype(np.int32)
    return a


def run_reference(part: dict, alts=None, matmul: str = "highest",
                  dtype=np.float64, device=None, **fault):
    """The reference over every stream of a part at once: a list of host
    dicts, one per stream, floating arrays in float64 (with `alts`, one
    `alt` input per stream)."""
    import jax
    import jax.numpy as jnp

    device = device if device is not None else jax.devices("cpu")[0]
    model = part["model"]
    ref = reference_module(model["cell"])
    streams = []
    for i, s in enumerate(part["streams"]):
        st = {k: s[k] for k in ref.INPUTS}
        if s.get("start") is not None:
            st["start"] = s["start"]
        if alts is not None:
            st["alt"] = alts[i]
        streams.append(st)
    stacked = jax.tree.map(lambda *xs: _as(np.stack(xs), dtype), *streams)
    shared = {"params0": part["params0"], "masks": part["masks"]}
    shared = jax.tree.map(lambda x: _as(x, dtype), shared)
    with jax.enable_x64(dtype == np.float64), jax.default_device(device):
        run = _compiled(model["cell"], json.dumps(model, sort_keys=True),
                        int(part["windows"]), matmul,
                        tuple(sorted(fault.items())))
        out = jax.device_get(run(jax.tree.map(jnp.asarray, shared),
                                 jax.tree.map(jnp.asarray, stacked)))
    return [jax.tree.map(lambda x: _as(x[i], np.float64), out)
            for i in range(len(streams))]


def _prefixed(part: dict, name: str) -> str:
    return f"{part['part']}.{name}" if part.get("part") else name


def check_part(part: dict, limits: dict) -> tuple[dict, dict]:
    """({name: value}, info) of one part; a number that no stream could
    give is missing."""
    refs = run_reference(part)
    streams = part["streams"]
    ref = reference_module(part["model"]["cell"])
    own = {k: limits[_prefixed(part, k)] for k in NUMBERS
           if _prefixed(part, k) in limits}
    best = [stream_numbers(s, r, part["params0"])
            for s, r in zip(streams, refs)]
    # other exact outcomes (near-ties): judge each stream by the closest
    alt_of, alt_in = [], []
    for i, r in enumerate(refs):
        for alt in getattr(ref, "alternatives", lambda _: [])(r):
            alt_of.append(i)
            alt_in.append(alt)
    if alt_in:
        alts = run_reference(dict(part, streams=[streams[i] for i in alt_of]),
                             alts=alt_in)
        for i, r in zip(alt_of, alts):
            nums = stream_numbers(streams[i], r, part["params0"])
            if _score(nums, own) < _score(best[i], own):
                best[i] = nums
    values = {}
    for name in NUMBERS:
        vals = [b[name] for b in best if name in b]
        if vals:
            values[_prefixed(part, name)] = max(vals)
    worst = [b["change_gap_worst_leaf"] for b in best
             if "change_gap_worst_leaf" in b]
    return values, {_prefixed(part, "near_ties_tried"): len(alt_in),
                    _prefixed(part, "change_gap_worst_leaf"):
                    max(worst, default=None)}


def check(record: list, limits: dict) -> tuple[dict, dict]:
    """({name: {"value", "limit"}}, info) for every number that `limits`
    names; a number that no stream of the record could give reads inf.  A
    number that `limits` does not name is reported in `info`, not
    compared."""
    values, info = {}, {}
    for part in record:
        v, i = check_part(part, limits)
        values.update(v)
        info.update(i)
    out = {name: {"value": values.get(name, float("inf")), "limit": lim}
           for name, lim in limits.items()}
    info.update({k: v for k, v in values.items() if k not in limits})
    return out, info
