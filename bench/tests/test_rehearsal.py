"""CPU rehearsal of each cell's code path through the harness's own
functions, at a tiny size."""
import math

import pytest

from bench.tests.rehearse import run_tiny

CELLS = ("n16-fleet1024", "n16-stream")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    out = run_tiny(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"]["stream_steps_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert math.isfinite(c["value"])


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_on_cpu_reports_no_device_metric(workload):
    """The CPU has no device plane: the readers that need one stay silent."""
    out = run_tiny(workload, trace=True)
    assert out["correct"], out["checks"]
    assert "update_chunk_ms_per_window" not in out["metrics"]
    assert "influence_roofline" not in out["metrics"]
