"""Weights and masks of a configuration, made by the benchmark.

Masks are part of the configuration: a fixed random pattern drawn from the
configuration's `mask_seed` with density 1 - omega on every input and
recurrent weight matrix (biases, thresholds and the readout stay dense, as
in the paper's Sec. 5).  Weights come from the run's `--seed`, made on the
device in one jitted call.  Both are flat dicts under the canonical leaf
names of `bench/references/egru.py`; `to_flat` gives them the tree shape
the program's single-layer learner takes, and `dense_influence` turns the
program's influence carry into the reference's dense layout.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

GATES = ("u", "r", "z")
MASKED = tuple(f"{g}.{w}" for g in GATES for w in ("W", "R"))


def leaf_shapes(model: dict) -> dict:
    n, n_in, n_out = model["n_hidden"], model["n_in"], model["n_out"]
    shapes = {}
    for g in GATES:
        shapes[f"{g}.W"] = (n_in, n)
        shapes[f"{g}.R"] = (n, n)
        shapes[f"{g}.b"] = (n,)
    shapes["theta"] = (n,)
    shapes["out.W"] = (n, n_out)
    shapes["out.b"] = (n_out,)
    return shapes


def masks(model: dict) -> dict:
    """{leaf: float32 [shape]} of kept weights, for the masked leaves."""
    rng = np.random.default_rng(int(model["mask_seed"]))
    shapes = leaf_shapes(model)
    return {name: (rng.random(shapes[name]) >= float(model["omega"]))
            .astype(np.float32) for name in MASKED}


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def params(model: dict, seed: int, mask: dict) -> dict:
    """Masked weights from the seed, on the device, in float32: inputs and
    recurrent weights N(0, 1/fan_in), biases 0, thresholds 0.1 |N(0, 1)|,
    readout N(0, 1/n)."""
    shapes = leaf_shapes(model)
    n, n_in = model["n_hidden"], model["n_in"]
    scale = {f"{g}.W": 1 / math.sqrt(n_in) for g in GATES}
    scale.update({f"{g}.R": 1 / math.sqrt(n) for g in GATES})
    scale["out.W"] = 1 / math.sqrt(n)
    names = sorted(shapes)

    @jax.jit
    def make(key, mask):
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {}
        for name in names:
            if name.endswith(".b"):
                out[name] = jnp.zeros(shapes[name], jnp.float32)
                continue
            x = jax.random.normal(keys[name], shapes[name], jnp.float32)
            if name == "theta":
                x = 0.1 * jnp.abs(x)
            else:
                x = scale[name] * x
            out[name] = x * mask[name] if name in mask else x
        return out

    return make(key_from_seed(seed), {k: jnp.asarray(v)
                                      for k, v in mask.items()})


def to_flat(tree: dict) -> dict:
    """Canonical leaves -> the single-layer tree {u: {W, R, b}, ..., theta,
    out: {W, b}}."""
    flat = {g: {w: tree[f"{g}.{w}"] for w in ("W", "R", "b")} for g in GATES}
    flat["theta"] = tree["theta"]
    flat["out"] = {"W": tree["out.W"], "b": tree["out.b"]}
    return flat


def from_program(tree: dict) -> dict:
    """A single-layer program tree -> canonical leaves."""
    out = {f"{g}.{w}": tree[g][w] for g in GATES for w in ("W", "R", "b")}
    out["theta"] = tree["theta"]
    out["out.W"], out["out.b"] = tree["out"]["W"], tree["out"]["b"]
    return out


def mask_tree(model: dict, mask: dict) -> dict:
    """The program's mask tree for one layer (no readout entry): masked
    leaves from `mask`, biases and thresholds all ones."""
    full = {name: jnp.asarray(mask[name]) if name in mask
            else jnp.ones(shape, jnp.float32)
            for name, shape in leaf_shapes(model).items()}
    tree = to_flat(full)
    del tree["out"]
    return tree


def flat_columns(model: dict, mask: dict) -> np.ndarray:
    """The live columns of the program's flat influence axis, ascending.

    The flat axis of a one-layer EGRU (kind "gru") holds, per gate in
    u, r, z order, one group of m = n_in + n + 1 columns per unit q: the
    unit's input-weight column W[:, q], its recurrent-weight column
    R[:, q] and its bias b[q]; then one column per threshold theta[q].  A
    column lives where the masks keep its weight."""
    n = model["n_hidden"]
    parts = []
    for g in GATES:
        groups = np.concatenate([np.asarray(mask[f"{g}.W"]).T,
                                 np.asarray(mask[f"{g}.R"]).T,
                                 np.ones((n, 1))], axis=1)
        parts.append(groups.reshape(-1))
    parts.append(np.ones(n))
    return np.nonzero(np.concatenate(parts) > 0)[0]


def dense_influence(model: dict, mask: dict, vals, idx) -> dict:
    """The program's column-compact, row-compact influence carry -> the
    reference's dense layout {leaf: [B, n, *leaf shape]} of d a / d leaf.

    vals [B, K, Pc_pad]: row s of example b is the influence of unit
    idx[b, s] (-1: no unit; the rows of other units are zero), and compact
    column c is the c-th live flat column (`flat_columns`; columns past
    them are padding)."""
    n, n_in = model["n_hidden"], model["n_in"]
    m = n_in + n + 1
    vals, idx = np.asarray(vals, np.float64), np.asarray(idx)
    live = flat_columns(model, mask)
    B = vals.shape[0]
    flat = np.zeros((B, n, len(GATES) * n * m + n))
    for b in range(B):
        rows = idx[b] >= 0
        flat[b, idx[b][rows][:, None], live[None, :]] = \
            vals[b, rows][:, :live.size]
    out = {}
    for i, g in enumerate(GATES):
        grp = flat[:, :, i * n * m:(i + 1) * n * m].reshape(B, n, n, m)
        out[f"{g}.W"] = grp[..., :n_in].transpose(0, 1, 3, 2)
        out[f"{g}.R"] = grp[..., n_in:n_in + n].transpose(0, 1, 3, 2)
        out[f"{g}.b"] = grp[..., n_in + n]
    out["theta"] = flat[:, :, len(GATES) * n * m:]
    return out
