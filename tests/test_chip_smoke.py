"""chip_smoke.py's phases rehearsed on the CPU at a small size.

The script itself refuses any device but a TPU; here its phase functions run
with the Pallas kernel in interpret mode, so a change that breaks a phase's
set-up, its gradient check or its record shows before a chip run.
"""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs import egru_spiral
from repro.core.cells import stacked_config
from repro.launch.serve import session_stream

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_before_any_phase(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main()
    assert e.value.code == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_precision_phase_interpreted(smoke):
    rec = smoke.phase_precision(interpret=True)
    assert rec["ok"], rec
    assert rec["fused_kernel"] < rec["fused_kernel_tol"]


def test_online_phase_interpreted(smoke):
    """Phase A's learner at n=16 with batch 4 and the kernel interpreted:
    the engine's gradients agree with the dense oracle within the stated
    reassociation bound, and the record holds every field the chip run
    prints."""
    layer = dataclasses.replace(egru_spiral.CONFIG, n_in=3, batch_size=4)
    rec = smoke.phase_online("A", stacked_config(layer, 1),
                             session_stream(0, 4, 3, layer.n_out),
                             interpret=True, require_kernel=False)
    assert rec["ok"], rec
    assert rec["grad_rel_err"] <= rec["grad_tol"]
    assert rec["carry_shape"][:2] == [4, 16]
    assert rec["warm_windows"] == smoke.WARM_WINDOWS


def test_fleet_phase_small(smoke):
    rec = smoke.phase_fleet(n=16, B=2, slots=2, sessions=3,
                            session_windows=2)
    assert rec["ok"], rec
    assert rec["joined"] == rec["left"] == 3
    assert rec["windows"] >= 3
