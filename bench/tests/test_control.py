"""The control of `correct`: the plain reference put in the program's
place, computed one precision below the configuration's (float32 matmuls as
three bf16 passes), has to come out not correct at each cell's limits; so
does the reference with half of every batch left out of the loss.  The
inputs and start states are those of a short program run of the cell
(`bench/readings.py` `program_record`).  Small sizes on the CPU; the chip
readings are in PERF.md."""
import functools

import pytest

from bench import check as CH
from bench import readings as RD
from bench import run as R

# the fleet's sessions at their own size, fewer of them; the stream as it is
SMALL = {"n16-fleet1024": ({}, {"slots": 16}), "n16-stream": ({}, {})}


def resolve_small(workload):
    r = R.resolve(workload)
    model, spec = SMALL[workload]
    r["config"]["model"].update(model)
    r["spec"].update(spec)
    return r


@functools.lru_cache(maxsize=None)
def record(workload, seed):
    return RD.program_record(resolve_small(workload), seed, seconds=0.5)


@pytest.mark.parametrize("how", [{"matmul": "bf16x3"},
                                 {"drop_half_batch": True}],
                         ids=["bf16x3", "half_batch"])
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload, how):
    limits = resolve_small(workload)["spec"]["limits"]
    checks, _ = CH.check(RD.stand_in(record(workload, 2 ** 40 + 3), **how),
                         limits)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_reference_in_float32_is_correct(workload):
    """The same stand-in at the configuration's own precision passes: the
    limits sit above float32 rounding."""
    limits = resolve_small(workload)["spec"]["limits"]
    checks, _ = CH.check(RD.stand_in(record(workload, 5)), limits)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
