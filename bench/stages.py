"""What the per-stage readers share: the program's own record of a traced
run, read in the process that ran it.

The program's tracer (`repro.obs.trace.current()`) keeps what a profile
alone does not say: the host spans the program opened (the fleet's
`fleet.admit` and `fleet.retire`, host bookkeeping that marks a slot for
reset, `fleet.slot_reset`, the one batched reset dispatch, `fleet.gather`,
`window`, `fleet.bookkeep`; the trainer's `window`), each with its start on
the profiler's clock and its duration, and for the update chunk a map from
each HLO instruction to the named scope it came from, noted under the name
the entry gives in `ctx["window"]["program"]` (`fleet_chunk`,
`online_chunk`).  A program that keeps neither gives no tracer, and every
reader then returns None.

Device stages: the self times of the chunk's ops (`ctx["trace"]["ops"]`,
from `bench/trace_reduce.py`) summed over the instructions of one stage,
per execution of the chunk.  The op times are keyed by instruction name
alone, so an instruction of another program with the same name (a copy in
the fleet's slot reset) counts too.

Host spans: the durations of the spans of one name that start between the
first and the last of the traced `window` spans, per interval between
windows, the basis of `host_gap_ms_per_window`.
"""
from __future__ import annotations

DEVICE_STAGES = ("partials", "j_tile_gather", "mbar_rows",
                 "influence_update", "grad_readout", "optimizer")
UNTRACED = "(no host span)"


def tracer():
    """The program's tracer, or None where the program has none."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    current = getattr(trace, "current", None)
    return current() if current is not None else None


def _program_stages(tr, ctx):
    """The noted stage map of the chunk the entry names
    (`ctx["window"]["program"]`), or of the only program noted where the
    context has no window record (the readers' tests in
    `tests/test_stage_tracing.py` build such a context)."""
    programs = getattr(tr, "programs", {}) if tr else {}
    name = ctx.get("window", {}).get("program")
    if name is None and len(programs) == 1:
        name = next(iter(programs))
    return programs.get(name)


def _runs(ctx, least: int):
    chunk = ctx["trace"]["chunk"]
    if chunk is None or chunk["runs"] < least:
        return None
    return chunk["runs"]


def device_ms(ctx, stage):
    """Device ms per window of the chunk's ops in `stage`; with stage None,
    of its ops in none of DEVICE_STAGES."""
    tr, runs = tracer(), _runs(ctx, 1)
    stages = _program_stages(tr, ctx)
    if not stages or runs is None:
        return None
    if stage is None:
        names = [i for i, s in stages.items() if s not in DEVICE_STAGES]
    else:
        names = [i for i, s in stages.items() if s == stage]
    ops = ctx["trace"]["ops"]
    return 1e3 * sum(ops.get(i, 0.0) for i in names) / runs


def host_ms(ctx, name):
    """Host ms per interval between the traced windows of the spans called
    `name`."""
    tr = tracer()
    if tr is None:
        return None
    spans = list(tr.spans)
    wins = [s for s in spans if s["name"] == "window"]
    wins = wins[-int(ctx["spec"]["trace_windows"]):]
    if len(wins) < 2:
        return None
    lo = wins[0]["start_ns"]
    hi = wins[-1]["start_ns"] + wins[-1]["dur_ns"]
    total = sum(s["dur_ns"] for s in spans
                if s["name"] == name and lo <= s["start_ns"] < hi)
    return 1e-6 * total / (len(wins) - 1)


def untraced_idle_ms(ctx):
    """Device-idle ms per interval between chunks under no host span."""
    runs = _runs(ctx, 2)
    if tracer() is None or runs is None:
        return None
    return 1e3 * ctx["trace"]["gaps"].get(UNTRACED, 0.0) / (runs - 1)
