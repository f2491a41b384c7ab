"""The reference found by its cell's name, and the EGRU reference started
from a saved state: from the zero state it is the reference from the zero
state; restarted from its own state after window 4 it gives window 5 as the
run through the whole history does; and the program's compact influence
carry, turned into the reference's layout, is the dense backend's carry."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as CH
from bench import model as M
from bench.traffic import generator as G

TOY = {"cell": "toy", "update_every": 3, "lr": 0.1}
SMALL = {"cell": "egru", "n_hidden": 4, "n_in": 2, "n_out": 2, "gamma": 1.0,
         "eps": 0.3, "omega": 0.5, "mask_seed": 3, "batch": 3,
         "update_every": 2, "lr": 0.05, "b1": 0.9, "b2": 0.95,
         "adam_eps": 1e-8}


def _toy_part(windows=2, B=4, d=3, seed=0):
    """A toy part whose 'program' outputs are SGD on linear regression,
    worked out here in numpy."""
    rng = np.random.default_rng(seed)
    k, lr = TOY["update_every"], TOY["lr"]
    xs = rng.standard_normal((windows * k, B, d))
    ys = rng.standard_normal((windows * k, B))
    w0 = rng.standard_normal(d)
    w, losses, grad1 = w0.copy(), [], None
    for i in range(windows):
        x, y = xs[i * k:(i + 1) * k], ys[i * k:(i + 1) * k]
        err = np.einsum("tbd,d->tb", x, w) - y
        losses.append(np.mean(err ** 2))
        g = 2 * np.einsum("tb,tbd->d", err, x) / err.size
        grad1 = {"w": g} if grad1 is None else grad1
        w = w - lr * g
    stream = {"name": "toy", "xs": xs, "ys": ys, "loss": losses,
              "grad1": grad1, "params": {"w": w}}
    return {"part": "", "model": TOY, "params0": {"w": w0}, "masks": {},
            "windows": windows, "streams": [stream]}


LIMITS = {"loss_gap": 1e-9, "grad1_gap": 1e-9, "change_gap_median": 1e-9}


def test_toy_reference_is_found_by_its_cell_name(monkeypatch):
    monkeypatch.setattr(CH, "REFERENCES", Path(__file__).parent / "references")
    part = _toy_part()
    checks, info = CH.check([part], LIMITS)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    assert info["near_ties_tried"] == 0       # the toy has no alternatives
    part["streams"][0]["loss"][1] *= 1.001
    checks, _ = CH.check([part], LIMITS)
    assert checks["loss_gap"]["value"] > LIMITS["loss_gap"]


def test_missing_reference_exits_with_code_2(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(CH, "REFERENCES", tmp_path)
    with pytest.raises(SystemExit) as e:
        CH.check([_toy_part()], LIMITS)
    assert e.value.code == 2
    assert "missing reference toy.py" in capsys.readouterr().err


# -- the EGRU reference from a state -----------------------------------------

def _egru_inputs(windows, seed=1):
    mask = M.masks(SMALL)
    with jax.enable_x64(False):
        p0 = {k: np.asarray(v, np.float64)
              for k, v in jax.device_get(M.params(SMALL, seed, mask)).items()}
    mix = {"kind": "stream", "inputs": "spiral", "spirals": 64, "seq_len": 5,
           "noise": 0.05}
    _, stream = G.Traffic(mix, seed, SMALL).session(0)
    xs, ys = G.window_inputs(stream, 0, windows * SMALL["update_every"])
    return p0, mask, xs, ys


def _run(windows, p0, mask, xs, ys, start=None):
    ref = CH.reference_module("egru")
    run = jax.jit(ref.make_reference(SMALL, windows))
    stream = {"xs": jnp.asarray(xs, jnp.float64), "ys": jnp.asarray(ys)}
    if start is not None:
        stream["start"] = jax.tree.map(jnp.asarray, start)
    shared = {"params0": p0, "masks": {k: np.asarray(v, np.float64)
                                       for k, v in mask.items()}}
    return jax.device_get(run(jax.tree.map(jnp.asarray, shared), stream))


def _close(a, b, rtol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=1e-14)


def test_start_from_the_zero_state_is_the_reference_from_zero():
    with jax.enable_x64(True):
        p0, mask, xs, ys = _egru_inputs(3)
        zeros = {k: np.zeros_like(v) for k, v in p0.items()}
        B, n = xs.shape[1], SMALL["n_hidden"]
        start = {"params": p0, "m": zeros, "v": zeros, "count": 0,
                 "state": np.zeros((B, n)),
                 "influence": {k: np.zeros((B, n) + v.shape)
                               for k, v in p0.items()
                               if not k.startswith("out.")}}
        plain = _run(3, p0, mask, xs, ys)
        restarted = _run(3, p0, mask, xs, ys, start)
    assert sorted(plain) == sorted(restarted)
    _close(plain, restarted, 1e-12)


def test_restart_at_window_4_matches_the_whole_history_to_window_5():
    """The state after 4 windows (parameters, moments, events, and the
    influence d a / d theta with the parameter trajectory held, by forward
    differentiation of the reference's own step), then one window: the
    same loss, moments and parameters as 5 windows from the zero state."""
    k = SMALL["update_every"]
    with jax.enable_x64(True):
        p0, mask, xs, ys = _egru_inputs(5)
        runs = {w: _run(w, p0, mask, xs[:w * k], ys[:w * k])
                for w in (1, 2, 3, 4, 5)}
        per_window = [p0] + [runs[w]["params"] for w in (1, 2, 3)]
        cell = CH.reference_module("egru").make_cell(SMALL)
        B, n = xs.shape[1], SMALL["n_hidden"]
        no_flip = jnp.zeros((B, n), bool)

        def state(delta):
            a = jnp.zeros((B, n))
            for t in range(4 * k):
                p = {name: per_window[t // k][name] + d
                     for name, d in delta.items()}
                a, _ = cell(p, a, xs[t], no_flip)
            return a

        zeros = {name: jnp.zeros_like(v) for name, v in p0.items()}
        influence = jax.jacfwd(state)(zeros)
        four = runs[4]
        start = {"params": four["params"], "m": four["m"], "v": four["v"],
                 "count": 4, "state": four["state"],
                 "influence": {name: np.asarray(v)
                               for name, v in influence.items()
                               if not name.startswith("out.")}}
        np.testing.assert_array_equal(np.asarray(state(zeros)), four["state"])
        one = _run(1, four["params"], mask, xs[4 * k:], ys[4 * k:], start)
    five = runs[5]
    np.testing.assert_allclose(one["loss"][0], five["loss"][4], rtol=1e-12)
    for name in ("params", "m", "v", "state"):
        _close(one[name], five[name], 1e-10)


def test_compact_carry_in_dense_layout_is_the_dense_backends_carry():
    """Both backends over the same stream for 3 windows: the compact_fused
    carry, turned into the reference's layout, against the dense backend's
    per-gate influence [B, k, q, group column] read by the same layout
    definition (a group is W[:, q], R[:, q], b[q])."""
    from repro.core.cells import EGRUConfig
    from repro.core.learner import LearnerSpec, make_learner
    from repro.optim import make_optimizer
    from repro.runtime.online import OnlineTrainer, OnlineTrainerConfig

    model = dict(SMALL, n_hidden=8, batch=4)
    n, n_in = model["n_hidden"], model["n_in"]
    mask = M.masks(model)
    params = M.to_flat(M.params(model, 5, mask))
    masks = dict(M.mask_tree(model, mask), out=None)
    mix = {"kind": "stream", "inputs": "spiral", "spirals": 64,
           "seq_len": 5, "noise": 0.05}
    _, stream = G.Traffic(mix, 5, model).session(0)
    ecfg = EGRUConfig(n_hidden=n, n_in=n_in, n_out=model["n_out"],
                      kind="gru", gamma=model["gamma"], eps=model["eps"],
                      batch_size=model["batch"], lr=model["lr"])
    carries = {}
    for backend in ("compact_fused", "dense"):
        learner = make_learner(LearnerSpec(engine="sparse", cfg=ecfg,
                                           backend=backend))
        t = OnlineTrainer(
            OnlineTrainerConfig(total_steps=3 * model["update_every"],
                                update_every=model["update_every"]),
            learner, make_optimizer("adamw", lr=model["lr"]), params,
            masks, stream)
        t.run()
        carries[backend] = jax.device_get(t.carry)
    c = carries["compact_fused"]
    got = M.dense_influence(model, mask, c["vals"], c["idx"])
    Md = carries["dense"]["M"]
    want = {"theta": Md["theta"]}
    for g in M.GATES:
        want[f"{g}.W"] = Md[g][..., :n_in].transpose(0, 1, 3, 2)
        want[f"{g}.R"] = Md[g][..., n_in:n_in + n].transpose(0, 1, 3, 2)
        want[f"{g}.b"] = Md[g][..., n_in + n]
    assert sorted(got) == sorted(want)
    assert np.abs(want["u.R"]).max() > 0
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
