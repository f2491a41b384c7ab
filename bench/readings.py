"""The readings the limits of `correct` are set from, for one cell.

    python3 bench/readings.py --workload <cell> --seeds 11 12 13 ... \
        [--control-seeds 21 22 23]

In one process, on the chip:

  program  a short run of the cell per seed (`run.run_cell`): the compared
           numbers of sound runs, whose largest is a limit's lower reading;
  control  the plain reference put in the program's place, computed at the
           next precision below the configuration's (float32 matmuls as
           three bf16 passes, XLA's `high`), on the inputs and from the
           start states of a short program run of the cell (the program's
           own outputs replaced): its numbers, whose smallest is a limit's
           upper reading;
  half_batch  the same reference at full precision with half of every
           batch left out of the loss (the mean taken over the rest): a
           fault the limits must also catch;
  float32  the same reference at the configuration's own precision
           (float32, matmuls at highest) on the same device: a witness of
           what float32 arithmetic alone reads, independent of the program.

A state left unchanged reads 1 on change_gap_median by construction and is
not run.
One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PROGRAM_SECONDS = 2.0     # a short window: the check reads set-up's windows


def program_record(r: dict, seed: int,
                   seconds: float = PROGRAM_SECONDS) -> list:
    """The check record of a short run of the cell's program: set-up, a
    span of `seconds` and whatever the entry runs after it for the check."""
    import shutil
    import tempfile

    from bench import run as R
    entry = R.load_module(r["entry"], "bench_entry")
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        cell = entry.Cell(r["config"], r["spec"], r["mix"], seed,
                          traced=False, workdir=work)
        cell.setup()
        cell.measure(seconds)
        record = cell.check_record()
        cell.free()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return record


def stand_in(record: list, **how) -> list:
    """The record with the reference's own outputs (run as `how` says, on
    the default device in float32) in the program's place: each loss, and
    each first gradient and final parameters the program reported."""
    import jax
    import numpy as np

    from bench import check as CH
    parts = []
    for part in record:
        outs = CH.run_reference(part, dtype=np.float32,
                                device=jax.devices()[0], **how)
        streams = [dict(s, loss=list(o["loss"][:len(s["loss"])]),
                        **{k: o[k] for k in ("grad1", "params")
                           if s.get(k) is not None})
                   for s, o in zip(part["streams"], outs)]
        parts.append(dict(part, streams=streams))
    return parts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import check as CH
    from bench import run as R

    r = R.resolve(R.check_name(args.workload))
    devs = R.setup_jax(int(r["cell"]["chips"]))
    limits = r["spec"]["limits"]
    for seed in args.seeds:
        out = R.run_cell(r, seed, PROGRAM_SECONDS, False, devs)
        print(json.dumps({"reading": "program", "seed": seed,
                          "checks": out["checks"], **out["check_info"],
                          "correct": out["correct"]}), flush=True)
    for seed in args.control_seeds:
        rec = program_record(r, seed)
        for name, how in (("control", {"matmul": "bf16x3"}),
                          ("half_batch", {"drop_half_batch": True}),
                          ("float32", {})):
            checks, info = CH.check(stand_in(rec, **how), limits)
            print(json.dumps({"reading": name, "seed": seed,
                              "checks": checks, **info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
