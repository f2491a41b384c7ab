"""Span tracing: nested host spans, and the stages of a compiled program.

A `Tracer` hands out `span("window")` context managers.  A completed span
records its name, its id and its parent's id, its start on `time.time_ns()`
(the clock of the profiler's host events), its duration, its self time (the
duration less the time its children cover), its nesting depth and its
arguments.  Spans about one fleet session carry its `sid`.

With ``jax_annotations=True`` every span also enters a
`jax.profiler.TraceAnnotation`, so when a profiler session is active
(`Telemetry.create(..., trace=True)`, or the benchmark's own) the host
spans land in the profiler's trace beside the device's ops, on one clock.
Without a profiler session the annotation is a no-op.

`note_program(name, jitted, *args)` maps each instruction of a compiled
program to the stage it belongs to: the first component of its HLO
`op_name` metadata that is one of `STAGES`, the `jax.named_scope`s that the
update chunk's code opens.  A device trace names ops by instruction, so the
map turns a profile's per-op times into per-stage times.

A tracer that is on registers itself as the process's tracer, `current()`:
the profiler session it feeds is process-wide too.  A disabled tracer
(`Tracer(enabled=False)`) records nothing and `span(...)` costs one `if`,
so the runtime can call it unconditionally.
"""
from __future__ import annotations

import collections
import contextlib
import re
import time

# bound memory on unbounded streams: keep the newest MAX_SPANS spans and
# count the ones pushed out
MAX_SPANS = 200_000

# the named scopes of the update chunk (`core/sparse_rtrl.py`,
# `core/learner.py`, `cells/mamba2.py`, `runtime/online.py`,
# `runtime/fleet.py`, `obs/metricpack.py`)
STAGES = ("partials", "j_tile_gather", "mbar_rows", "influence_update",
          "grad_readout", "optimizer", "telemetry", "ssm_trace_update",
          "frozen_blocks", "ssm_readout")

_NULL = contextlib.nullcontext()
_current: "Tracer | None" = None


def current() -> "Tracer | None":
    """The process's tracer: the newest `Tracer` made with enabled=True."""
    return _current


class Tracer:
    def __init__(self, enabled: bool = True, jax_annotations: bool = False):
        global _current
        self.enabled = enabled
        self.jax_annotations = jax_annotations
        self.spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
        self.dropped = 0
        self.programs: dict[str, dict[str, str | None]] = {}
        self._stack: list[list] = []        # open spans: [id, child ns]
        self._next_id = 0
        if enabled:
            _current = self

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL
        return self._span(name, args)

    @contextlib.contextmanager
    def _span(self, name: str, args: dict):
        ann = _NULL
        if self.jax_annotations:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation(name)
        sid = self._next_id
        self._next_id += 1
        parent = self._stack[-1][0] if self._stack else None
        frame = [sid, 0]
        self._stack.append(frame)
        t0 = time.time_ns()
        try:
            with ann:
                yield
        finally:
            dur = time.time_ns() - t0
            self._stack.pop()
            if self._stack:
                self._stack[-1][1] += dur
            if len(self.spans) == MAX_SPANS:
                self.dropped += 1
            self.spans.append({"id": sid, "parent": parent, "name": name,
                               "start_ns": t0, "dur_ns": dur,
                               "self_ns": dur - frame[1],
                               "depth": len(self._stack), "args": args})

    def note_program(self, name: str, jitted, *args) -> dict | None:
        """Store and return `{instruction: stage}` for the program that
        `jitted` runs on `args` (arrays or `jax.ShapeDtypeStruct`s).  Call it
        after the program's first dispatch, so that the compile is served
        from JAX's caches.  A disabled tracer notes nothing."""
        if not self.enabled:
            return None
        text = jitted.lower(*args).compile().as_text()
        self.programs[name] = program_stages(text)
        return self.programs[name]


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _stage(op_name: str) -> str | None:
    """The first path component that names a stage; a component wrapped by
    a transformation (`vmap(optimizer)`, `transpose(jvp(partials))`) counts
    by the name inside."""
    for part in op_name.split("/"):
        part = part.rsplit("(", 1)[-1].rstrip(")")
        if part in STAGES:
            return part
    return None


def program_stages(hlo_text: str) -> dict[str, str | None]:
    """`{instruction: stage or None}` from a compiled module's HLO text: the
    stage its `op_name` metadata names (None without one)."""
    stages = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is not None:
            op = _OP_NAME.search(line)
            stages[m.group(1)] = _stage(op.group(1)) if op else None
    return stages
