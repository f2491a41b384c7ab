"""BENCHMARK.json against its contract, and every cell resolving its files
by name; the harness refusing a run it cannot make."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as R
from bench.peaks import TABLE, peak

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    names = []
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        names.append(c["name"])
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["config"], w["traffic"]]
    for kind in ("end_to_end", "per_layer"):
        for m in BM[kind]:
            assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
            assert m["better"] in ("lower", "higher")
            names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in BM["workloads"]])
def test_cell_resolves_every_file_by_name(workload):
    r = R.resolve(workload)
    assert r["entry"].is_file()
    assert r["config"]["name"] == r["cell"]["config"]
    for kind in ("end_to_end", "per_layer"):
        assert r["metrics"][kind], kind
        for m in r["metrics"][kind]:
            reader = R.load_module(R.BENCH / "metrics" / f"{m['name']}.py",
                                   "reader")
            assert callable(reader.read)
    from bench import check as CH
    assert r["spec"]["limits"]
    for name in r["spec"]["limits"]:
        assert name.rsplit(".", 1)[-1] in CH.NUMBERS, name
    assert {"loss_gap", "grad1_gap", "change_gap_median"} \
        <= set(r["spec"]["limits"])
    assert CH.reference_path(r["config"]["model"]["cell"]).is_file()


def test_peaks_table():
    v5e = peak("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peak("cpu")
    assert all("source" in row for row in TABLE.values())


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "n16-fleet1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu_before_measuring():
    p = _run(ROOT, {})
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
