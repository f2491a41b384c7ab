"""The granite period's cell, `ghm-top10-docs-b6`, through the harness's own
functions on the CPU at a small size with the published structure (a
period of three layers with one attention layer, d_model 64, four heads of
8, d_state 16, vocab 256, three rows, two local heads of the four, short
documents so that rows reset inside the checked windows): its reference
against the plain one the program's tests use, its start from a saved
state, its traffic, its check and the faults the check has to catch."""
import functools
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as CH
from bench import readings as RD
from bench import run as R
from bench.tests.test_faults import FAULTS
from bench.traffic import docs as DOCS

CELL = "ghm-top10-docs-b6"
SEED = 2 ** 33 + 17
TINY = {"hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
        "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "layer_types": ["mamba", "attention", "mamba"], "local_heads": [0, 2]}
TINY_MODEL = {"layers_held": [0, 3], "batch": 3,
              "update_every": 4, "kv_slots": 32, "lr": 1e-3}
TINY_MIX = {"length_median": 6, "length_min": 2, "length_max": 12}


def resolve_tiny() -> dict:
    r = R.resolve(CELL)
    r["config"].update(TINY)
    r["config"]["model"].update(TINY_MODEL)
    r["mix"].update(TINY_MIX)
    r["spec"]["trace_windows"] = 3
    return r


def run_tiny(trace: bool = False, seed: int = SEED) -> dict:
    return R.run_cell(resolve_tiny(), seed, 0.3, trace, jax.devices())


@functools.lru_cache(maxsize=None)
def _test_reference():
    spec = importlib.util.spec_from_file_location(
        "mamba2_ref", R.ROOT / "tests" / "mamba2_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _program_record(seed=SEED):
    """Set-up's windows, a span of one window, and the restarted one (its
    saved traces held by the reference until the next record)."""
    return RD.program_record(resolve_tiny(), seed, seconds=0.0)


def test_cell_runs_and_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["stream_steps_per_s"]["value"] > 0
    assert set(out["checks"]) == set(resolve_tiny()["spec"]["limits"])


def test_traced_run_on_cpu_reports_no_device_metric():
    out = run_tiny(trace=True)
    assert out["correct"], out["checks"]
    for name in ("ssm_trace_update_ms_per_window", "ssm_trace_roofline",
                 "frozen_blocks_ms_per_window", "ssm_readout_ms_per_window",
                 "ssm_step_mfu"):
        assert name not in out["metrics"]


def test_reference_agrees_with_the_plain_reference_of_the_tests():
    """The benchmark's reference (float64, trunk once, host contraction)
    and the plain float32 reference of `tests/mamba2_ref.py` (every step
    whole) give the same losses, first gradient and parameters."""
    from bench.entries.online_ssm_stream import cell_config
    part = _program_record()[0]
    ours = CH.run_reference(part)[0]
    s = part["streams"][0]
    cfg = cell_config(part["model"])
    flat = part["params0"]
    ref = CH.reference_module("mamba2")
    learned = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32),
                           ref._unflat({k: v for k, v in flat.items()
                                        if not k.startswith("frozen.")}))
    frozen = jax.tree.map(jnp.asarray, ref._unflat(
        {k[7:]: v for k, v in flat.items() if k.startswith("frozen.")}))
    frozen["layers"] = tuple(frozen["layers"])
    m = part["model"]
    losses, grads, params = _test_reference().run(
        cfg, learned, frozen, s["tokens"], s["resets"], s["targets"],
        m["update_every"], part["windows"], m["lr"], m["b1"], m["b2"])
    np.testing.assert_allclose(ours["loss"], np.asarray(losses), rtol=1e-5)
    for name, g in ref._flat(grads[0]).items():
        scale = np.abs(ours["grad1"][name]).max()
        np.testing.assert_allclose(g, ours["grad1"][name], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
    for name, p in ref._flat(params[-1]).items():
        dp = np.asarray(p, np.float64) - flat[name]
        dr = ours["params"][name] - flat[name]
        np.testing.assert_allclose(dp, dr, rtol=1e-3,
                                   atol=1e-4 * np.abs(dr).max(),
                                   err_msg=name)


def test_start_from_a_saved_state_equals_the_whole_history():
    """The reference restarted from the program's saved state (its traces
    held on the host) gives the window that the reference run from the
    zero state through the whole history gives."""
    rec = _program_record()
    restart = rec[1]
    s = restart["streams"][0]
    pos = int(s["name"].split("@")[1])
    k = restart["model"]["update_every"]
    whole = dict(rec[0], windows=pos // k + 1)
    r = resolve_tiny()
    stream = DOCS.DocStream(r["mix"], SEED, r["config"]["model"]["batch"],
                            r["config"]["vocab_size"])
    w0 = DOCS.window(stream, 0, pos + k)
    whole["streams"] = [dict(rec[0]["streams"][0], **w0)]
    full = CH.run_reference(whole)[0]
    part = CH.run_reference(restart)[0]
    np.testing.assert_allclose(part["loss"][0], full["loss"][-1], rtol=1e-5)
    for name in part["params"]:
        dr = full["params"][name] - s["start"]["params"][name]
        dp = part["params"][name] - s["start"]["params"][name]
        np.testing.assert_allclose(dp, dr, rtol=1e-2,
                                   atol=1e-3 * np.abs(dr).max(),
                                   err_msg=name)


def test_documents_are_deterministic_and_reset_where_they_end():
    r = resolve_tiny()
    mix, B, V = r["mix"], 3, 256
    a, b = DOCS.DocStream(mix, SEED, B, V), DOCS.DocStream(mix, SEED, B, V)
    wa, wb = DOCS.window(a, 0, 200), DOCS.window(b, 0, 200)
    for key in wa:
        np.testing.assert_array_equal(wa[key], wb[key])
    other = DOCS.window(DOCS.DocStream(mix, SEED + 1, B, V), 0, 200)
    assert (other["tokens"] != wa["tokens"]).any()
    # replayed from the middle without the steps before it
    late = DOCS.window(DOCS.DocStream(mix, SEED, B, V), 120, 40)
    np.testing.assert_array_equal(late["tokens"], wa["tokens"][120:160])
    for row in range(B):
        starts = np.flatnonzero(wa["resets"][:, row])
        assert starts[0] == 0
        lengths = np.diff(starts)
        assert lengths.min() >= mix["length_min"]
        assert lengths.max() <= mix["length_max"]
        assert all(a.length(row, d) == n for d, n in enumerate(lengths))
    # the target is the row's next token, across a document's end too
    np.testing.assert_array_equal(wa["targets"][:-1], wa["tokens"][1:])
    assert wa["tokens"].max() < V and wa["tokens"].min() >= 0


@functools.lru_cache(maxsize=None)
def _control_record():
    """One record for the three stand-ins, which run one after another
    (the reference holds one record's saved traces at a time)."""
    return _program_record(SEED + 1)


@pytest.mark.parametrize("how", [{"matmul": "bf16x3"},
                                 {"drop_half_batch": True}, {}],
                         ids=["bf16x3", "half_batch", "float32"])
def test_control_fails_and_float32_passes(how):
    """The reference in the program's place (`bench/readings.py`): one
    precision below the configuration's (bf16x3) and with half of every
    batch out of the loss it is not correct at the cell's limits; at the
    configuration's own precision it is."""
    limits = resolve_tiny()["spec"]["limits"]
    checks, _ = CH.check(RD.stand_in(_control_record(), **how), limits)
    failed = any(c["value"] > c["limit"] for c in checks.values())
    assert failed is bool(how), checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_correct_false(fault, monkeypatch):
    if fault == "half_batch":
        _half_batch_granite(monkeypatch)
    else:
        FAULTS[fault](monkeypatch)
    out = run_tiny()
    assert out["correct"] is False, out["checks"]


def test_half_batch_in_the_restarted_window_alone_fails_it(monkeypatch):
    """Half of the batch out of the loss in the restarted window only: the
    set-up windows pass and the restart part fails, `restart.loss_gap`
    among its numbers."""
    from repro.runtime.online import OnlineTrainer
    orig = OnlineTrainer._ckpt_tree

    def ckpt_tree(self):
        # the entry reads the saved state just before the restarted window
        _half_batch_granite(monkeypatch)
        jax.clear_caches()          # the chunk is traced again, faulty
        return orig(self)
    monkeypatch.setattr(OnlineTrainer, "_ckpt_tree", ckpt_tree)
    checks = run_tiny()["checks"]
    setup = {k: c for k, c in checks.items() if "." not in k}
    assert setup and all(c["value"] <= c["limit"]
                         for c in setup.values()), checks
    gap = checks["restart.loss_gap"]
    assert gap["value"] > gap["limit"], checks


def _half_batch_granite(monkeypatch):
    """The period's loss is the mean over the first half of the rows."""
    import repro.cells.mamba2 as M
    orig = M.head_loss

    def head_loss(cfg, frozen, r, y_t, tt):
        h = y_t.shape[0] // 2
        loss, logits = orig(cfg, frozen, r[:h], y_t[:h], tt)
        return loss, logits
    monkeypatch.setattr(M, "head_loss", head_loss)
