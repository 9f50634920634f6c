"""The harness: every cell runs end to end at a small size, everything is
found by name, and a run that cannot measure prints no result."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, run_cell

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_small_and_is_correct(layout, cell):
    spec = json.loads((layout / "BENCHMARK.json").read_text())
    res = run_cell(layout, cell)
    assert res["correct"], res["checks"]
    want = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def _hashes(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_config_mix_and_metric_are_found_by_name(layout):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric as new files (and entries in BENCHMARK.json); no file the
    harness already has changes."""
    before = _hashes(layout)
    conf = json.loads((layout / "bench/configs/epfl9-lib12.json").read_text())
    conf["name"] = "tiny-lib12"
    conf["circuits"] = ["log2"]
    (layout / "bench/configs/tiny-lib12.json").write_text(json.dumps(conf))
    (layout / "bench/traffic/sweep.mc2.json").write_text(
        json.dumps({"generator": "sweep", "variants": 2, "sigma": 0.2}))
    (layout / "bench/metrics/sweep.calls_per_s.py").write_text(
        "def read(m):\n"
        "    return m['counters']['calls'] / m['window'].seconds\n")
    spec = json.loads((layout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-lib12", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny-lib12.json", "why": "test"})
    spec["workloads"].append({"name": "sweep.tiny.mc2", "config": "tiny-lib12",
                              "traffic": "sweep.mc2", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "sweep_designs_per_s":
            m["workloads"].append("sweep.tiny.mc2")
    spec["per_layer"].append({"name": "sweep.calls_per_s", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry point", "moves": "sweep_designs_per_s",
                              "workloads": ["sweep.tiny.mc2"]})
    (layout / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _hashes(layout)
    assert all(after[p] == h for p, h in before.items())

    res = run_cell(layout, "sweep.tiny.mc2", seconds=1.0)
    assert res["correct"]
    assert set(res["metrics"]) == {"sweep_designs_per_s", "setup_s"}
    res = run_cell(layout, "sweep.tiny.mc2", seconds=1.0, trace=1)
    assert res["metrics"]["sweep.calls_per_s"]["value"] > 0
    assert res["counters"]["calls"] >= 1


def _cli(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_any_platform_but_a_tpu(layout):
    proc = _cli(layout)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks
    for kind, p in peaks.items():
        assert p["source"] and all(v > 0 for k, v in p.items() if k != "source"), kind
