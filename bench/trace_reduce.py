"""Profiler trace -> device busy and idle time, per-program and per-op time,
and idle gaps attributed to what the host was doing.

Reads the `.xplane.pb` that `jax.profiler` writes (or a gzipped copy),
with JAX's own `ProfileData`.  On a TPU the device plane (`/device:TPU:0`)
carries a line of XLA op events and a line of XLA module (program)
executions; host planes carry the spans that `jax.profiler.TraceAnnotation`
wrote, on the same clock.  An op event is named by its HLO text; the
reduction names it by the instruction alone (`fusion.213`, `while.2`).  A
loop's op event holds the events of its body's ops: each op is counted for
its own time, less that of the ops nested in it.

The traced span runs from the start of the first host span named `window`
(the program's own per-window span) to the end of the last one.  Within it:

  busy_s        the union of the device op intervals
  ops           device seconds per op name, each op less its nested ops
  chunk         the update chunk: the program holding the most device time;
                its busy union, its executions (one per `window` span, the
                ops that start inside it), and the idle time between one
                execution's last op and the next one's first
  gaps          idle stretches, each named after the innermost host span on
                the thread that wrote the `window` spans
"""
from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from pathlib import Path

SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(tdir) -> Path:
    found = sorted(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return found[-1]


def union(intervals) -> list:
    """Merge [start, end) intervals into a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged: list, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by the merged intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def op_name(hlo_text: str) -> str:
    """`%fusion.213 = f32[...] fusion(...)` -> `fusion.213`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%") if " = " in hlo_text \
        else hlo_text


def self_times(ops: list) -> list:
    """Each op's duration less that of the ops nested in it (events of one
    line either nest or are disjoint)."""
    own = [e - s for s, e, *_ in ops]
    stack = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1])):
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def load(path) -> dict:
    """{"device", "ops": [(start, end, name, program)], "host": {thread:
    [(start, end, name)]}} in nanoseconds, from the first TPU core's plane.
    An op's program is its `hlo_module` and `program_id` stats where the
    trace gives them, else the module execution on the device's module
    line that contains it."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    ops, modules, host = [], [], defaultdict(list)
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            device = plane.name
            for e in lines[OPS_LINE].events:
                st = dict(e.stats)
                prog = (f"{st.get('hlo_module', '')}({st['program_id']})"
                        if "program_id" in st else None)
                ops.append((e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name), prog))
            if MODULES_LINE in lines:
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name)
                                 for e in lines[MODULES_LINE].events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host[f"{plane.name}/{line.name}"].append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name))
    starts = [m[0] for m in modules]
    tagged = []
    for s, e, name, prog in ops:
        if prog is None:
            k = bisect.bisect_right(starts, s) - 1
            prog = modules[k][2] if k >= 0 and s < modules[k][1] else None
        tagged.append((s, e, name, prog))
    return {"device": device, "ops": sorted(tagged), "host": dict(host)}


def summarize(path) -> dict:
    return reduce(load(path))


def reduce(t: dict) -> dict:
    """The summary of a loaded trace (see the module docstring)."""
    spans = sorted((s, e) for evs in t["host"].values() for s, e, n in evs
                   if n == SPAN)
    ops = t["ops"]
    if spans:
        lo, hi = spans[0][0], max(e for _, e in spans)
    elif ops:
        lo, hi = ops[0][0], max(o[1] for o in ops)
    else:
        lo = hi = 0
    inside = [(max(s, lo), min(e, hi), name, prog)
              for s, e, name, prog in ops if e > lo and s < hi]
    busy = union((s, e) for s, e, _, _ in inside)
    op_s = defaultdict(float)
    prog_ops = defaultdict(list)
    for (s, e, name, prog), own in zip(inside, self_times(inside)):
        op_s[name] += own * 1e-9
        prog_ops[prog].append((s, e))
    progs = {p: union(iv) for p, iv in prog_ops.items()}
    chunk = None
    if progs and spans:
        name = max(progs, key=lambda p: covered(progs[p], lo, hi))
        # each window runs the chunk once, inside the window's own span
        execs = []
        for s, e in spans:
            iv = [x for x in progs[name] if s <= x[0] < e]
            if iv:
                execs.append((iv[0][0], max(x[1] for x in iv)))
        idle = sum((b0 - a1) - covered(busy, a1, b0)
                   for (_, a1), (b0, _) in zip(execs, execs[1:]))
        chunk = {"program": name, "runs": len(execs),
                 "busy_s": covered(progs[name], lo, hi) * 1e-9,
                 "idle_between_s": idle * 1e-9}
    return {"device": t["device"], "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "ops": dict(op_s), "chunk": chunk,
            "gaps": _gaps(busy, lo, hi, t["host"])}


def _gaps(busy: list, lo: float, hi: float, host: dict,
          short_ns: float = 10e3) -> dict:
    """Idle seconds per innermost host span of the `window` thread; idle
    stretches under `short_ns` (between back-to-back ops) are summed under
    one label."""
    thread = next((th for th, evs in host.items()
                   if any(n == SPAN for _, _, n in evs)), None)
    evs = sorted(host.get(thread, []))
    out = defaultdict(float)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < short_ns:
            out["(between ops, under 10 us)"] += (b - a) * 1e-9
            continue
        mid = 0.5 * (a + b)
        inner = [ev for ev in evs if ev[0] <= mid < ev[1]]
        label = min(inner, key=lambda ev: ev[1] - ev[0])[2] if inner \
            else "(no host span)"
        out[label] += (b - a) * 1e-9
    return dict(out)


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's `breakdown`: the device ops that took most time
    and the host spans under which the device sat idle longest."""
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
