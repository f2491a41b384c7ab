"""How `correct` is decided: what the timed path produced against the plain
reference (`bench/reference.py`), run in float64 on the host's CPU.

A cell's record (`Cell.check_record()`) holds, for each checked stream, its
inputs, the window losses the program read back, and, where the program's
state could be read at the window boundaries, the first gradient as the
optimizer got it (AdamW's first moment after one update, over 1 - b1) and the
parameters after the last checked window.  Three numbers are compared, each
the worst over the streams:

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

The EGRU's Heaviside makes a unit's event depend on the sign of its
pre-activation v.  Where the reference finds |v| under `TIE_MARGIN`, float32
rounding may decide the event either way, and both outcomes are exact
results.  The reference is then also run with each such event flipped, and
the stream is judged against whichever outcome it matches best.
"""
from __future__ import annotations

import functools
import json

import numpy as np

NUMBERS = ("loss_gap", "grad1_gap", "change_gap_median")
TIE_MARGIN = 1e-5     # |v| under this: float32 may take the event either way
MAX_TIES = 4          # near-ties tried per stream


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
        dp = {k: s["params"][k] - params0[k] for k in names}
        dr = {k: ref["params"][k] - params0[k] for k in names}
        gaps = leaf_gaps(dp, dr, names)
        out["change_gap_median"] = float(np.median(gaps))
        out["change_gap_worst_leaf"] = float(np.max(gaps))
    return out


def _score(nums: dict, limits: dict) -> float:
    return max(v / limits[k] for k, v in nums.items() if k in limits)


@functools.lru_cache(maxsize=16)
def _compiled(model_json: str, windows: int, matmul: str, fault: tuple):
    """The jitted reference, vmapped over streams (one per set of settings,
    so near-tie reruns reuse its compile)."""
    import jax

    from bench.reference import make_reference
    run = make_reference(json.loads(model_json), windows, matmul=matmul,
                         **dict(fault))
    return jax.jit(jax.vmap(run, in_axes=(None, None, 0, 0, 0)))


def run_reference(record: dict, flips=None, matmul: str = "highest",
                  dtype=np.float64, device=None, **fault):
    """The reference over every stream of the record at once: a list of
    {loss, grad1, params, v} host dicts, one per stream (or per row of
    `flips`, which then pairs with `streams` index list)."""
    import jax
    import jax.numpy as jnp

    device = device if device is not None else jax.devices("cpu")[0]
    streams = record["streams"]
    W = int(record["windows"])
    xs = np.stack([s["xs"] for s in streams]).astype(dtype)
    ys = np.stack([s["ys"] for s in streams]).astype(np.int32)
    n = record["model"]["n_hidden"]
    if flips is None:
        flips = np.zeros(xs.shape[:3] + (n,), bool)
    with jax.enable_x64(dtype == np.float64), jax.default_device(device):
        run = _compiled(json.dumps(record["model"], sort_keys=True), W,
                        matmul, tuple(sorted(fault.items())))
        p0 = {k: jnp.asarray(v, dtype) for k, v in record["params0"].items()}
        mk = {k: jnp.asarray(v, dtype) for k, v in record["masks"].items()}
        out = jax.device_get(run(p0, mk, jnp.asarray(xs), jnp.asarray(ys),
                                 jnp.asarray(flips)))
    res = []
    for i in range(len(streams)):
        res.append({"loss": np.asarray(out["loss"][i], np.float64),
                    "grad1": {k: np.asarray(v[i], np.float64)
                              for k, v in out["grad1"].items()},
                    "params": {k: np.asarray(v[i], np.float64)
                               for k, v in out["params"].items()},
                    "v": np.asarray(out["v"][i])})
    return res


def check(record: dict, limits: dict) -> tuple[dict, dict]:
    """({number: {"value", "limit"}}, {"near_ties_tried",
    "change_gap_worst_leaf"}) for the record; a number that no stream could
    give reads inf."""
    refs = run_reference(record)
    streams = record["streams"]
    best = [stream_numbers(s, r, record["params0"])
            for s, r in zip(streams, refs)]
    # near-tie events: rerun each tied stream with one event flipped
    alt_streams, alt_flips, alt_of = [], [], []
    for i, r in enumerate(refs):
        ties = np.argwhere(np.abs(r["v"]) < TIE_MARGIN)[:MAX_TIES]
        for t in ties:
            f = np.zeros(r["v"].shape, bool)
            f[tuple(t)] = True
            alt_streams.append(streams[i])
            alt_flips.append(f)
            alt_of.append(i)
    if alt_streams:
        alts = run_reference(dict(record, streams=alt_streams),
                             flips=np.stack(alt_flips))
        for i, r in zip(alt_of, alts):
            nums = stream_numbers(streams[i], r, record["params0"])
            if _score(nums, limits) < _score(best[i], limits):
                best[i] = nums
    out = {}
    for name in NUMBERS:
        vals = [b[name] for b in best if name in b]
        out[name] = {"value": max(vals) if vals else float("inf"),
                     "limit": limits[name]}
    worst = [b["change_gap_worst_leaf"] for b in best
             if "change_gap_worst_leaf" in b]
    return out, {"near_ties_tried": len(alt_streams),
                 "change_gap_worst_leaf": max(worst, default=None)}
