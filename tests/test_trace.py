"""Program spans (`repro.runtime.trace`) as a profiler trace records them.

Each case runs one entry point under `jax.profiler.start_trace` inside an
outer ``bench.window`` span, as the benchmark harness does, reads the
``.xplane.pb`` back with `jax.profiler.ProfileData`, and checks the spans'
nesting and arguments against what the run did: the device calls counted
by wrapping the jitted callables, and the bytes of their host operands.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import batch, circuits as C  # noqa: E402
from repro.core.aig import AigStats  # noqa: E402
from repro.core.explorer import explore_suite  # noqa: E402
from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable  # noqa: E402
from repro.core.transforms import CharacterizationCache, characterize_suite  # noqa: E402
from repro.kernels import aig_sim  # noqa: E402
from repro.runtime import trace  # noqa: E402

SRC = Path(__file__).resolve().parents[1] / "src"
DEPTH1 = [("Ba",), ("Rf",), ("Rs",), ("Rw",)]


def _record(trace_dir: Path, fn) -> list[dict]:
    """Run ``fn`` traced inside ``bench.window``; return the window and
    every ``rcim.*`` event as dicts, each with its innermost enclosing
    event on the same host line (``parent``)."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == "bench.window" or ev.name.startswith(trace.PREFIX):
                    s = int(ev.start_ns)
                    events.append(dict(name=ev.name, start=s, end=s + int(ev.duration_ns),
                                       line=(plane.name, li), args=dict(ev.stats)))
    for ev in events:
        outer = [o for o in events if o is not ev and o["line"] == ev["line"]
                 and o["start"] <= ev["start"] and ev["end"] <= o["end"]]
        ev["parent"] = max(outer, key=lambda o: o["start"], default=None)
    return events


def _named(events, name):
    return [ev for ev in events if ev["name"] == name]


def _parent_name(ev):
    return ev["parent"]["name"] if ev["parent"] is not None else None


def _ancestors(ev):
    out = []
    while ev["parent"] is not None:
        ev = ev["parent"]
        out.append(ev["name"])
    return out


def _host_nbytes(args) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(args)
               if isinstance(x, (np.ndarray, np.generic)))


def test_explore_suite_spans(tmp_path, monkeypatch):
    suite = {"adder": C.gen_adder(6), "max": C.gen_max(6, 4)}
    cha = characterize_suite(suite, DEPTH1, n_jobs=1, backend="python")
    table = ModelTable.monte_carlo(n=2, sigma=0.1, seed=3)
    explore_suite(suite, TOPOLOGY_LIBRARY, DEPTH1, cha=cha, model_sweep=table)
    # the traced calls run on distinct copies of the records (the runner
    # shares one record between recipes with equal outputs), the first
    # call building every level-op matrix
    fresh = {n: {r: AigStats.from_dict(s.to_dict()) for r, s in m.items()}
             for n, m in cha.items()}
    n_records = sum(map(len, fresh.values()))

    fused_suite = batch._fused_program()
    calls = []

    def counted(*args, **kw):
        calls.append(_host_nbytes(args))
        return fused_suite(*args, **kw)

    monkeypatch.setattr(batch, "_FUSED_SUITE", counted)
    events = _record(tmp_path / "trace", lambda: explore_suite(
        suite, TOPOLOGY_LIBRARY, DEPTH1, cha=fresh, model_sweep=table))

    (top,) = _named(events, "rcim.explore_suite")
    assert _parent_name(top) == "bench.window"
    assert top["args"] == {"circuits": 2, "variants": 2}
    for name in ("feasible", "suite_table", "fused", "assemble"):
        (ev,) = _named(events, f"rcim.explore.{name}")
        assert ev["parent"] is top
    (table_span,) = _named(events, "rcim.explore.suite_table")
    assert table_span["args"] == {"circuits": 2, "recipes": len(DEPTH1) + 1,
                                  "built": n_records, "reused": 0}
    (fused,) = _named(events, "rcim.explore.fused")
    (dispatch,) = _named(events, "rcim.batch.dispatch")
    (fetch,) = _named(events, "rcim.batch.fetch")
    assert dispatch["parent"] is fused and fetch["parent"] is fused
    assert dispatch["end"] <= fetch["start"]
    assert len(calls) == 1
    # one device: every operand is placed once (tests/test_shard.py has four)
    assert dispatch["args"] == {"h2d_bytes": calls[0], "devices": 1,
                                "placed_bytes": calls[0]}
    (shard,) = _named(events, "rcim.batch.shard")
    assert shard["parent"] is dispatch
    assert shard["args"] == {"devices": 1, "variants_per_device": 2}
    assert fetch["args"]["d2h_bytes"] > 0 and fetch["args"]["devices"] == 1

    # a second call over the same records reuses every matrix
    events = _record(tmp_path / "trace2", lambda: explore_suite(
        suite, TOPOLOGY_LIBRARY, DEPTH1, cha=fresh, model_sweep=table))
    (table_span,) = _named(events, "rcim.explore.suite_table")
    assert table_span["args"]["built"] == 0
    assert table_span["args"]["reused"] == n_records


def test_assemble_span_counts_views(tmp_path):
    """Assembly makes each circuit's `VariationGrid` and its nominal
    grid as deferred device slices, and reads none of their fields."""
    suite = {"adder": C.gen_adder(6), "max": C.gen_max(6, 4)}
    cha = characterize_suite(suite, DEPTH1, n_jobs=1, backend="python")
    table = ModelTable.monte_carlo(n=2, sigma=0.1, seed=3)
    events = _record(tmp_path / "trace", lambda: explore_suite(
        suite, TOPOLOGY_LIBRARY, DEPTH1, cha=cha, model_sweep=table))
    (assemble,) = _named(events, "rcim.explore.assemble")
    assert assemble["args"] == {"views": 2 * len(suite), "view_slices": 0}


def test_characterize_suite_spans(tmp_path, monkeypatch):
    rtl = C.gen_adder(8)
    characterize_suite({"adder": rtl}, DEPTH1, n_jobs=1, backend="device")

    mega, sig = aig_sim._jnp_mega_fn(), aig_sim._jnp_sig_fn()
    launches = {"n": 0, "h2d": 0}

    def counted(fn, host_args):
        def call(*args):
            launches["n"] += 1
            launches["h2d"] += sum(args[i].nbytes for i in host_args)
            return fn(*args)
        return call

    # the mega program's third operand is the cached elementary tables,
    # already on the device
    monkeypatch.setattr(aig_sim, "_JNP_MEGA", counted(mega, (0, 1, 3)))
    monkeypatch.setattr(aig_sim, "_JNP_SIG", counted(sig, (0, 1)))
    cache = CharacterizationCache(tmp_path / "cha")
    events = _record(tmp_path / "trace", lambda: characterize_suite(
        {"adder": rtl}, DEPTH1, cache=cache, n_jobs=1, backend="device"))

    (top,) = _named(events, "rcim.cha.suite")
    assert _parent_name(top) == "bench.window"
    assert top["args"] == {"circuits": 1, "recipes": len(DEPTH1)}
    for name in ("warm_start", "apply", "stats", "persist"):
        assert _named(events, f"rcim.cha.{name}")
        assert all(ev["parent"] is top for ev in _named(events, f"rcim.cha.{name}"))
    (warm,) = _named(events, "rcim.cha.warm_start")
    assert warm["args"] == {"preloaded": 0}

    applies = _named(events, "rcim.cha.apply")
    assert sorted(ev["args"]["transform"] for ev in applies) == sorted(t for (t,) in DEPTH1)
    assert all(ev["args"]["n_ands"] == rtl.n_ands for ev in applies)
    for name in ("candidates", "cones", "synth", "rebuild"):
        assert _named(events, f"rcim.cha.{name}")
        assert all(_parent_name(ev) == "rcim.cha.apply"
                   for ev in _named(events, f"rcim.cha.{name}"))
    for name in ("compile", "pack", "launch", "unpack"):
        assert _named(events, f"rcim.aig_sim.{name}")
        assert all("rcim.cha.apply" in _ancestors(ev)
                   for ev in _named(events, f"rcim.aig_sim.{name}"))
    assert all(ev["args"]["n_nodes"] == rtl.n_nodes
               for ev in _named(events, "rcim.aig_sim.compile"))
    # Ba has no device part
    (ba,) = [ev for ev in applies if ev["args"]["transform"] == "Ba"]
    assert not [ev for ev in events if ev["parent"] is ba]

    launch_spans = _named(events, "rcim.aig_sim.launch")
    assert launches["n"] > 0 and len(launch_spans) == launches["n"]
    assert all(ev["args"]["engine"] == "jnp" and ev["args"]["queries"] > 0
               for ev in launch_spans)
    assert sum(ev["args"]["h2d_bytes"] for ev in _named(events, "rcim.aig_sim.pack")) \
        == launches["h2d"]


def test_cones_span_counts_pairs(tmp_path):
    """A device rewrite records one ``rcim.cha.cones`` span whose
    ``pairs`` are its queries' summed cone sizes."""
    from repro.core import transforms as T

    rtl = C.gen_adder(8)
    events = _record(tmp_path / "trace", lambda: T.rewrite(rtl, backend="device"))
    (cones,) = _named(events, "rcim.cha.cones")
    cuts = T._enumerate_cuts(rtl, k=4, max_cuts=8)
    reach = T._reachable(rtl)
    queries = [(n, c) for n in range(rtl.n_pis + 1, rtl.n_nodes) if reach[n]
               for c in cuts[n] if len(c) >= 2 and n not in c]
    assert set(cones["args"]) == {"queries", "pairs", "rounds"}
    assert cones["args"]["queries"] == len(queries)
    assert cones["args"]["pairs"] == sum(len(rtl.cone_nodes(n, set(c))) for n, c in queries)
    assert 1 <= cones["args"]["rounds"] <= cones["args"]["pairs"]


def test_span_imports_nothing():
    """Without jax a span never imports it; with jax and no profiler
    session it imports nothing either."""
    code = (
        "import sys\n"
        "from repro.runtime import trace\n"
        "with trace.span('x', n=1) as s:\n"
        "    s.set_metadata(m=2)\n"
        "assert 'jax' not in sys.modules, sorted(sys.modules)\n"
        "import jax\n"
        "before = set(sys.modules)\n"
        "with trace.span('x', n=1) as s:\n"
        "    s.set_metadata(m=2)\n"
        "assert set(sys.modules) == before, set(sys.modules) - before\n"
    )
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
