"""Chip benchmark of online exact RTRL: one cell per call.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (`src/repro`).  The
cell is an entry of `BENCHMARK.json`'s `workloads`; everything about it is
found by name:

  bench/workloads/<cell>.json    the entry that drives it and its settings
                                 (a `learner` object there overrides the
                                 configuration's learner settings)
  bench/configs/<config>.json    the model, learner and optimizer
  bench/traffic/<mix>.json       the traffic mix (`bench/traffic/generator.py`)
  bench/metrics/<metric>.py      one reader per metric, `read(ctx)`
  bench/entries/<entry>.py       how the program's entry point is driven
  bench/references/<cell>.py     the plain reference of the configuration's
                                 `model.cell` (`bench/check.py`)

A run builds the cell from the seed, warms up its shapes by driving its
first update windows (set-up), then measures whole update windows until
`--seconds` have passed (`--trace 0`) or traces a few windows with the
profiler (`--trace 1`).  It then reads the device's peak memory, takes
what the check compares (an entry may run more untimed windows for it),
frees the program's state, and checks what the timed path produced against
the plain reference (`bench/check.py`).  The last line of standard output
is one JSON object: correct, attempted, failed, metrics, device[,
breakdown], checks.

Exits 2 where the program's sources are missing, 3 where JAX finds no TPU or
fewer chips than the cell asks for; nothing is printed to standard output
then.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


def fail(code: int, msg: str):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def check_name(name: str) -> str:
    if not name or len(name) > 64 or not set(name) <= NAME_CHARS \
            or name[0] in ".-":
        fail(2, f"bad name {name!r}")
    return name


def load_json(path: Path) -> dict:
    if not path.is_file():
        fail(2, f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> dict:
    """Everything a cell needs, found by the names in BENCHMARK.json."""
    bm = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        fail(2, f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = cfgs[check_name(cell["config"])]
    spec = load_json(BENCH / "workloads" / f"{check_name(workload)}.json")
    mix = load_json(BENCH / "traffic" / f"{check_name(cell['traffic'])}.json")
    config = load_json(ROOT / cfg_entry["file"])
    config["learner"] = dict(config["learner"], **spec.get("learner", {}))
    from bench import check as CH
    CH.reference_module(check_name(config["model"]["cell"]))

    def wanted(m):
        return "workloads" not in m or workload in m["workloads"]

    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = []
        for m in bm[kind]:
            if wanted(m):
                path = BENCH / "metrics" / f"{check_name(m['name'])}.py"
                if not path.is_file():
                    fail(2, f"missing reader {path.relative_to(ROOT)}")
                metrics[kind].append(m)
    entry = BENCH / "entries" / f"{check_name(spec['entry'])}.py"
    if not entry.is_file():
        fail(2, f"missing entry {entry.relative_to(ROOT)}")
    return {"bench": bm, "cell": cell, "spec": spec, "mix": mix,
            "config": config, "metrics": metrics, "entry": entry}


def read_metrics(defs: list, ctx: dict) -> dict:
    out = {}
    for m in defs:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def setup_jax(chips: int):
    """The device check and the persistent compile cache, before any
    compile.  The cache sits at a fixed path in the checkout unless
    JAX_COMPILATION_CACHE_DIR names one."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(3, f"no TPU: JAX's first device is {devs[0].platform} "
                f"({devs[0].device_kind})")
    if len(devs) < chips:
        fail(3, f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devs


class CompileCounter:
    """Counts the traces and backend compiles JAX reports while on."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1


def run_cell(r: dict, seed: int, seconds: float, trace: bool, devs) -> dict:
    """Set up, measure (or trace), read memory, free, check: the result
    line's object.  `devs` are the devices the run was given."""
    from bench import check as CH
    entry = load_module(r["entry"], "bench_entry")
    counter = CompileCounter()
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    t0 = time.perf_counter()
    try:
        cell = entry.Cell(r["config"], r["spec"], r["mix"], seed,
                          traced=trace, workdir=work)
        cell.setup()
        setup_s = time.perf_counter() - t0 + r.get("startup_s", 0.0)
        counter.on = True
        if trace:
            tdir = work / "profile"
            win = cell.trace(int(r["spec"]["trace_windows"]), tdir)
        else:
            win = cell.measure(seconds)
        counter.on = False
        stats = devs[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log = [f"compiles_in_window: {counter.count}"]
        ctx = {"window": win, "setup_s": setup_s, "peak_bytes": peak,
               "config": r["config"], "spec": r["spec"],
               "device_kind": devs[0].device_kind, "log": log}
        breakdown = None
        if trace:
            from bench import trace_reduce as TR
            ctx["trace"] = TR.summarize(TR.find_xplane(tdir))
            breakdown = TR.breakdown(ctx["trace"])
        metrics = read_metrics(
            r["metrics"]["per_layer" if trace else "end_to_end"], ctx)
        record = cell.check_record()
        cell.free()
        gc.collect()
        checks, info = CH.check(record, r["spec"]["limits"])
        log += [f"{k}: {v!r}" for k, v in info.items()]
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for line in log:
        print(line, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": win["attempted"], "failed": win["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check_info"] = info
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail(2, "--seed must be >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        fail(2, "the program's sources (src/repro) are not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    r = resolve(check_name(args.workload))
    devs = setup_jax(int(r["cell"]["chips"]))
    r["startup_s"] = time.perf_counter() - t_start
    out = run_cell(r, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
