"""The program-span readers (`program_spans` and the per-layer metrics
built on it), on small traces recorded on the CPU.

A synthetic trace records the program's spans (`repro.runtime.trace`)
around sleeps inside ``bench.window``, with a gap outside any span;
device-busy intervals are laid into its reduction by hand, as the CPU
has no device plane.  Each reader's number is checked against a plain
pass over the same events.  The small traced runs of each cell check
that every new metric of the cell reads a number from the program."""

from __future__ import annotations

import dataclasses
import json
import time
import types
from pathlib import Path

import pytest

import common
import program_spans
import trace_reduce
from conftest import ROOT, run_cell

jax = pytest.importorskip("jax")
from repro.runtime.trace import span  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["sweep.table_ms", "sweep.fused_host_ms", "sweep.h2d_mb", "sweep.assemble_ms",
       "cha.transform_host_ms", "cha.sim_host_ms", "cha.sim_launches", "cha.stats_ms"]
APPS = 2
GAP_S = 0.004


def _sleep(s=0.002):
    time.sleep(s)


def _program():
    _sleep(GAP_S)  # inside the window, outside any program span
    for _ in range(2):
        with span("explore_suite", circuits=2, variants=2):
            with span("explore.feasible"):
                _sleep(0.001)
            with span("explore.suite_table", circuits=2, recipes=5):
                _sleep()
            with span("explore.fused"):
                with span("batch.dispatch", h2d_bytes=1000):
                    _sleep()
                with span("batch.fetch") as sp:
                    _sleep()
                    sp.set_metadata(d2h_bytes=64)
                _sleep(0.001)
            with span("explore.assemble"):
                _sleep()
    with span("cha.suite", circuits=1, recipes=1):
        for t in ("Rw", "Ba"):
            with span("cha.apply", transform=t, n_ands=10):
                _sleep()
                if t == "Rw":
                    with span("aig_sim.compile", n_nodes=20):
                        _sleep(0.001)
                    with span("aig_sim.pack", h2d_bytes=512):
                        _sleep(0.001)
                    with span("aig_sim.launch", engine="jnp", w=1, queries=4):
                        _sleep()
                    with span("aig_sim.unpack"):
                        _sleep(0.001)
            with span("cha.stats", n_ands=9):
                _sleep(0.001)
            with span("cha.persist"):
                _sleep(0.001)


def _record(work_dir: Path, program) -> str:
    jax.profiler.start_trace(str(work_dir / "trace"))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            program()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.find_xplane(str(work_dir / "trace"))


def _events(path: str) -> list:
    """(name, start, end, args) of every host event: the plain pass."""
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), dict(ev.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events]


def _overlap(a, b, ivs) -> int:
    return sum(max(0, min(b, e) - max(a, s)) for s, e in ivs)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    work_dir = tmp_path_factory.mktemp("cell")
    path = _record(work_dir, _program)
    events = _events(path)
    red = trace_reduce.reduce(path)
    # Device-busy intervals laid by hand: the middle half of each fused
    # call and of the launch, and one interval outside every span.
    busy = []
    for name, s, e, _ in events:
        if name in ("rcim.explore.fused", "rcim.aig_sim.launch"):
            q = (e - s) // 4
            busy.append((s + q, e - q))
    (w0, _w1) = red.window
    busy.append((w0 + 1_000_000, w0 + 2_000_000))  # inside the leading gap
    busy.sort()
    red = dataclasses.replace(red, devices=["/device:TPU:0"],
                              busy={"/device:TPU:0": busy})
    m = dict(trace=red, counters={"applications": APPS}, window=None,
             ctx=types.SimpleNamespace(work_dir=work_dir))
    return m, events, busy


def _reader(name):
    return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                              "test_metric_" + name.replace(".", "_"))


def _plain(name, events, busy) -> float:
    def evs(n):
        return [(s, e, a) for en, s, e, a in events if en == n]

    def total(n):
        return sum(e - s for s, e, _ in evs(n))

    calls = len(evs("rcim.explore_suite"))
    sim = [(s, e) for en, s, e, _ in events if en.startswith("rcim.aig_sim.")]
    if name == "sweep.table_ms":
        return total("rcim.explore.suite_table") / calls / 1e6
    if name == "sweep.fused_host_ms":
        return sum(e - s - _overlap(s, e, busy) for s, e, _ in evs("rcim.explore.fused")) \
            / calls / 1e6
    if name == "sweep.h2d_mb":
        return sum(a["h2d_bytes"] for *_, a in evs("rcim.batch.dispatch")) / calls / 1e6
    if name == "sweep.assemble_ms":
        return total("rcim.explore.assemble") / calls / 1e6
    if name == "cha.transform_host_ms":
        return sum(e - s - _overlap(s, e, sim) for s, e, _ in evs("rcim.cha.apply")) \
            / APPS / 1e6
    if name == "cha.sim_host_ms":
        return sum(e - s - _overlap(s, e, busy) for s, e in sim) / APPS / 1e6
    if name == "cha.sim_launches":
        return len(evs("rcim.aig_sim.launch")) / APPS
    if name == "cha.stats_ms":
        return (total("rcim.cha.stats") + total("rcim.cha.persist")) / APPS / 1e6
    raise KeyError(name)


@pytest.mark.parametrize("name", NEW)
def test_reader_matches_a_plain_pass(traced, name):
    m, events, busy = traced
    got = _reader(name).read(m)
    assert got == pytest.approx(_plain(name, events, busy), rel=1e-9)
    assert got > 0


def test_every_new_metric_is_listed_for_its_cell():
    per_layer = {p["name"]: p for p in SPEC["per_layer"]}
    for name in NEW:
        cell = "sweep.lib12.mc16" if name.startswith("sweep.") else "cha.cut16k.cold"
        assert per_layer[name]["workloads"] == [cell]
        assert per_layer[name]["source"] == "device_trace"


def test_self_time_and_busy_within(traced):
    m, events, busy = traced
    ps = program_spans.load(m)
    for top in ps.named("rcim.explore_suite"):
        kids = [sp for sp in ps.spans if sp.line == top.line and sp.depth == top.depth + 1
                and top.start <= sp.start and sp.end <= top.end]
        assert [sp.name for sp in kids] == ["rcim.explore.feasible", "rcim.explore.suite_table",
                                            "rcim.explore.fused", "rcim.explore.assemble"]
        assert top.self_ns == (top.end - top.start) - sum(sp.end - sp.start for sp in kids)
    for sp in ps.named("rcim.explore.fused"):
        assert ps.busy_s(sp) == pytest.approx(_overlap(sp.start, sp.end, busy) / 1e9)
    (rw,) = [sp for sp in ps.named("rcim.cha.apply") if sp.args["transform"] == "Rw"]
    assert rw.args == {"transform": "Rw", "n_ands": 10}


def test_idle_by_span_fills_the_idle_time(traced):
    m, events, busy = traced
    red = m["trace"]
    ps = program_spans.load(m)
    parts = ps.idle_by_span()
    idle_s = red.window_s - red.busy_s()
    assert sum(parts.values()) == pytest.approx(idle_s, rel=1e-9)
    # the leading gap, less the busy interval laid in it, is outside any span
    first = min(sp.start for sp in ps.spans)
    lead = (first - red.window[0] - _overlap(red.window[0], first, busy)) / 1e9
    assert lead > 0.5 * GAP_S
    before = [(n, s, e) for n, s, e in ps.idle_pieces() if e <= first]
    assert {n for n, _, _ in before} == {program_spans.NONE}
    assert sum(e - s for _, s, e in before) / 1e9 == pytest.approx(lead, rel=1e-9)
    assert parts[program_spans.NONE] >= lead
    # idle time of a leaf is its duration less the busy time inside it
    launch = ps.named("rcim.aig_sim.launch")
    assert parts["rcim.aig_sim.launch"] == pytest.approx(
        sum(sp.seconds - ps.busy_s(sp) for sp in launch), rel=1e-9)
    # a parent holds only the idle time its children leave (no busy
    # interval lies in an application's own time)
    assert parts["rcim.cha.apply"] == pytest.approx(
        sum(sp.self_ns for sp in ps.named("rcim.cha.apply")) / 1e9, rel=1e-9)
    pieces = ps.idle_pieces()
    assert all(s < e for _, s, e in pieces)
    assert all(a[2] <= b[1] for a, b in zip(pieces, pieces[1:]))


def test_a_trace_without_program_spans_reads_none(tmp_path):
    path = _record(tmp_path, lambda: _sleep())
    m = dict(trace=trace_reduce.reduce(path), counters={"applications": APPS},
             window=None, ctx=types.SimpleNamespace(work_dir=tmp_path))
    assert program_spans.load(m) is None
    assert all(_reader(name).read(m) is None for name in NEW)


@pytest.mark.parametrize("cell", ["sweep.lib12.mc16", "cha.cut16k.cold"])
def test_traced_cell_reads_its_program_spans(layout, cell):
    res = run_cell(layout, cell, seconds=1.0, trace=1)
    want = {p["name"] for p in SPEC["per_layer"]
            if p["name"] in NEW and cell in p["workloads"]}
    assert want and want <= set(res["metrics"])
    assert all(res["metrics"][n]["value"] > 0 for n in want)
