"""Shared fixtures: a throwaway copy of the benchmark's layout, cut to a
size a CPU test run holds.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests`` from the
checkout's root."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

#: The small cut every test layout takes: each configuration's two
#: smallest circuits, recipes of length <= 2, the characterization window
#: at depth <= 2 and the service at 20 queries a second.
SMALL = dict(n_circuits=2, max_length=2, max_depth=2, rate_per_s=20)
#: The service mix, which has no cell in BENCHMARK.json yet (PERF.md,
#: Open questions): its generator, checks and faults are tested through
#: this entry of its own.
SERVICE = {"name": "serve.lib12.rerank", "config": "epfl9-lib12",
           "traffic": "serve.rerank", "chips": 1}


def make_layout(dst: Path, small: dict | None = SMALL) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``dst``, link the
    program beside them, and shrink the configurations and mixes to
    ``small`` (None keeps their sizes)."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    os.symlink(ROOT / "src", dst / "src")
    if small is None:
        return dst
    for p in (dst / "bench" / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        c["circuits"] = sorted(c["circuits"], key=c["and_nodes"].get)[: small["n_circuits"]]
        c["recipes"]["max_length"] = small["max_length"]
        p.write_text(json.dumps(c))
    for p in (dst / "bench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        if "max_depth" in t:
            t["max_depth"] = small["max_depth"]
        if "rate_per_s" in t:
            t["rate_per_s"] = small["rate_per_s"]
        p.write_text(json.dumps(t))
    return dst


@pytest.fixture
def layout(tmp_path) -> Path:
    return make_layout(tmp_path)


def run_cell(root: Path, cell: str, seconds: float = 2.0, seed: int = 2**31 + 11,
             trace: int = 0) -> dict:
    """One run of a cell in ``root`` with the chip check skipped."""
    import run

    return run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(trace)], root=root, require_tpu=False)


def run_mix(root: Path, cell: dict, seconds: float = 2.0, seed: int = 2**31 + 11) -> dict:
    """Set-up, window and check of a cell entry that BENCHMARK.json need
    not hold; returns each number compared, with its limit."""
    import run

    _spec, ctx, generator = run.prepare(root, cell, seed, seconds, False)
    run._jax_env(root)
    state = generator.setup(ctx)
    win = generator.window(ctx, state)
    return {c.name: {"value": c.value, "limit": c.limit}
            for c in generator.check(ctx, state, win)}
