"""The trace reduction, on a trace recorded on a TPU v5 lite: a
one-second traced run of the sweep generator over the default-width
suite and a 36-topology grid (three ``bench.sweep.call`` spans in
``bench.window``), kept small by dropping
the host's own events other than the harness's spans and the device
lines other than ``XLA Modules`` and ``XLA Ops``, then gzipped.  Each
number is checked against a plain pass over the same trace."""

from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import trace_reduce

TRACE = Path(__file__).parent / "data" / "sweep-1s.xplane.pb.gz"


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "sweep.xplane.pb"
    path.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def red(xplane):
    return trace_reduce.reduce(xplane)


@pytest.fixture(scope="module")
def profile(xplane):
    from jax.profiler import ProfileData

    return ProfileData.from_file(xplane)


def test_finds_the_chip_and_the_window(red, profile):
    assert red.devices == ["/device:TPU:0"]
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for p in profile.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events if ev.name == "bench.window"]
    assert len(spans) == 1
    assert red.window == (int(spans[0][0]), int(spans[0][1]))
    assert 0.9 < red.window_s < 5.0


def test_busy_is_the_union_of_op_intervals(red, profile):
    dev = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = next(line for line in dev.lines if line.name == "XLA Ops")
    lo, hi = red.window
    # plain pass: mark every op's clipped interval, merge by sweeping
    ivs = sorted((max(int(e.start_ns), lo), min(int(e.start_ns + e.duration_ns), hi))
                 for e in ops.events
                 if e.start_ns + e.duration_ns > lo and e.start_ns < hi)
    total, cur = 0, lo
    for s, e in ivs:
        if e > cur:
            total += e - max(s, cur)
            cur = e
    assert red.busy_s() == pytest.approx(total / 1e9, rel=1e-12)
    assert 0 < red.busy_s() < red.window_s


def test_idle_gaps_and_busy_fill_the_window(red):
    gaps = red.idle_gaps()
    assert sum(s for _, s in gaps) + red.busy_s() == pytest.approx(red.window_s, rel=1e-9)
    names = {n for n, _ in gaps}
    assert names <= {"bench.sweep.call", "bench.window"}
    assert "bench.sweep.call" in names


def test_program_time_per_call(red, profile):
    dev = next(p for p in profile.planes if p.name == "/device:TPU:0")
    mods = next(line for line in dev.lines if line.name == "XLA Modules")
    lo, hi = red.window
    fused = sum(min(e.start_ns + e.duration_ns, hi) - max(e.start_ns, lo)
                for e in mods.events if e.name.startswith("jit_fn(")
                and e.start_ns + e.duration_ns > lo and e.start_ns < hi) / 1e9
    assert fused > 0
    assert red.program_seconds("jit_fn(")["/device:TPU:0"] == pytest.approx(fused, rel=1e-12)
    assert len(red.spans_named("bench.sweep.call")) == 3
    assert red.top_ops(3) and red.top_ops(3)[0][1] > 0
