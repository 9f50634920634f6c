"""The four-chip sweep cell's readers (`bench/metrics/x4.*`,
``device_idle_pct.x4``), on a small trace recorded on the CPU.

A synthetic trace records two harness calls (``bench.sweep.call``), each
around the program's spans of one `explore_suite` call as the sharded
path records them, inside ``bench.window``.  Four device planes are laid
into its reduction by hand, as the CPU has none: uneven program times,
one collective operation beside the compute, and busy intervals inside
each dispatch.  Each reader's number is checked against a plain pass
over the same events."""

from __future__ import annotations

import dataclasses
import json
import time
import types

import pytest

import common
import trace_reduce
from conftest import ROOT

jax = pytest.importorskip("jax")
from repro.runtime.trace import span  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "sweep.lib12.mc1024.x4"
NEW = ["x4.fused_ms", "x4.chip_skew_pct", "x4.collective_ms", "x4.placed_mb",
       "x4.dispatch_ms", "x4.assemble_ms", "device_idle_pct.x4"]
DEVICES = [f"/device:TPU:{i}" for i in range(4)]
CALLS = 2
#: Per chip over the window: the fused program's seconds (uneven, chip 2
#: the slowest), and each operation's.
PROGRAM_S = [0.0030, 0.0032, 0.0040, 0.0031]
OPS = [{"fusion.7": 0.0020, "all-gather-start.1": c, "all-gather-done.1": c / 2,
        "copy.2": 0.0001} for c in (0.0004, 0.0006, 0.0005, 0.0003)]
PLACED = 57_000_000


def _sleep(s=0.002):
    time.sleep(s)


def _program(placed: bool):
    for _ in range(CALLS):
        with jax.profiler.TraceAnnotation("bench.sweep.call"):
            with span("explore_suite", circuits=9, variants=1024):
                with span("explore.fused"):
                    with span("batch.dispatch", h2d_bytes=1000) as sp:
                        with span("batch.shard") as sh:
                            _sleep(0.001)
                            sh.set_metadata(devices=4, variants_per_device=256)
                        if placed:
                            sp.set_metadata(devices=4, placed_bytes=PLACED)
                        _sleep()
                    with span("batch.fetch", devices=4) as sp:
                        _sleep()
                        sp.set_metadata(d2h_bytes=64)
                with span("explore.assemble"):
                    _sleep(0.003)


def _record(work_dir, placed=True) -> str:
    jax.profiler.start_trace(str(work_dir / "trace"))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            _program(placed)
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.find_xplane(str(work_dir / "trace"))


def _events(path: str) -> list:
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), dict(ev.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events]


def _four_chips(red, events, devices=DEVICES):
    """The reduction with four device planes: chip i busy over the middle
    (i + 1) / 8 of each dispatch."""
    busy = {}
    for i, d in enumerate(devices):
        busy[d] = []
        for name, s, e, _ in events:
            if name == "rcim.batch.dispatch":
                q = (e - s) * (i + 1) // 16
                busy[d].append(((s + e) // 2 - q, (s + e) // 2 + q))
    return dataclasses.replace(
        red, devices=list(devices), busy=busy,
        programs={d: {"jit_fn(12)": PROGRAM_S[i], "jit_convert(3)": 0.001}
                  for i, d in enumerate(devices)},
        ops={d: dict(OPS[i]) for i, d in enumerate(devices)})


def _inputs(work_dir, red):
    return dict(trace=red, counters={}, window=None,
                ctx=types.SimpleNamespace(work_dir=work_dir))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    work_dir = tmp_path_factory.mktemp("cell")
    path = _record(work_dir)
    events = _events(path)
    return _inputs(work_dir, _four_chips(trace_reduce.reduce(path), events)), events


def _reader(name):
    return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                              "test_metric_" + name.replace(".", "_"))


def _overlap(a, b, ivs) -> float:
    return sum(max(0, min(b, e) - max(a, s)) for s, e in ivs)


def _plain(name, m, events) -> float:
    red = m["trace"]

    def evs(n):
        return [(s, e, a) for en, s, e, a in events if en == n]

    calls = len(evs("bench.sweep.call"))
    if name == "x4.fused_ms":
        return max(PROGRAM_S) / calls * 1e3
    if name == "x4.chip_skew_pct":
        return 100 * (max(PROGRAM_S) - min(PROGRAM_S)) / max(PROGRAM_S)
    if name == "x4.collective_ms":
        return max(o["all-gather-start.1"] + o["all-gather-done.1"] for o in OPS) \
            / calls * 1e3
    if name == "x4.placed_mb":
        return PLACED * len(evs("rcim.batch.dispatch")) / calls / 1e6
    if name == "x4.dispatch_ms":
        # device busy inside a span is the mean over the chips
        return sum(e - s - sum(_overlap(s, e, red.busy[d]) for d in DEVICES) / len(DEVICES)
                   for s, e, _ in evs("rcim.batch.dispatch")) / calls / 1e6
    if name == "x4.assemble_ms":
        return sum(e - s for s, e, _ in evs("rcim.explore.assemble")) / calls / 1e6
    if name == "device_idle_pct.x4":
        lo, hi = red.window
        busy = sum(_overlap(lo, hi, red.busy[d]) for d in DEVICES) / len(DEVICES)
        return 100 * (1 - busy / (hi - lo))
    raise KeyError(name)


@pytest.mark.parametrize("name", NEW)
def test_reader_matches_a_plain_pass(traced, name):
    m, events = traced
    got = _reader(name).read(m)
    assert got == pytest.approx(_plain(name, m, events), rel=1e-9)
    assert got > 0


def test_every_new_metric_is_listed_for_the_four_chip_cell():
    per_layer = {p["name"]: p for p in SPEC["per_layer"]}
    (cell,) = [w for w in SPEC["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "sweep_designs_per_s"
    (e2e,) = [m for m in SPEC["end_to_end"] if m["name"] == "sweep_designs_per_s"]
    assert CELL in e2e["workloads"]


def test_a_program_that_counts_no_placement_reads_none(tmp_path):
    """The parent program's dispatch span has no ``placed_bytes``: that
    metric reads nothing and the others still read."""
    path = _record(tmp_path, placed=False)
    m = _inputs(tmp_path, _four_chips(trace_reduce.reduce(path), _events(path)))
    assert _reader("x4.placed_mb").read(m) is None
    assert _reader("x4.dispatch_ms").read(m) > 0


def test_one_chip_reads_no_skew(tmp_path):
    path = _record(tmp_path)
    m = _inputs(tmp_path, _four_chips(trace_reduce.reduce(path), _events(path),
                                      devices=DEVICES[:1]))
    assert _reader("x4.chip_skew_pct").read(m) is None
    assert _reader("x4.fused_ms").read(m) == pytest.approx(PROGRAM_S[0] / CALLS * 1e3)
