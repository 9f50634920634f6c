"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with `jax.profiler.ProfileData`, nothing else.  What it gives:

* device busy time: per device, the union of the intervals in which an
  operation ran, clipped to the measured window (the host span
  ``bench.window``), and the idle share that follows;
* device time per named program (the ``XLA Modules`` line) and per
  operation (the ``XLA Ops`` line), per device, within the window;
* the harness's host spans (``bench.*`` `TraceAnnotation` events), on
  the same clock as the device events;
* the longest idle gaps of the window, each named by the innermost
  host span that covers it.

A device plane is an accelerator's (``/device:TPU:<n>``); the runtime's
other ``/device:`` planes (``/device:CUSTOM:...``) hold no operations.
Where a device plane has no ``XLA Ops`` line, every event of the plane
counts as an operation.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _covered(intervals, lo: int, hi: int) -> int:
    return sum(e - s for s, e in _clip(intervals, lo, hi))


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]                  # ns, on the trace clock
    devices: list[str]
    busy: dict[str, list[tuple[int, int]]]   # device -> union of op intervals
    programs: dict[str, dict[str, float]]    # device -> program -> seconds
    ops: dict[str, dict[str, float]]         # device -> op name -> seconds
    spans: list[tuple[str, int, int]]        # harness host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device: str | None = None) -> float:
        """Busy seconds in the window: one device's, or the mean over
        devices."""
        devs = [device] if device else self.devices
        if not devs:
            return 0.0
        tot = sum(_covered(self.busy[d], *self.window) for d in devs)
        return tot / len(devs) / 1e9

    def busy_within(self, lo: int, hi: int, device: str | None = None) -> float:
        """Busy seconds of one device (or the mean over devices) in
        [lo, hi)."""
        devs = [device] if device else self.devices
        if not devs:
            return 0.0
        return sum(_covered(self.busy[d], lo, hi) for d in devs) / len(devs) / 1e9

    def spans_named(self, name: str) -> list[tuple[int, int]]:
        return [(s, e) for n, s, e in self.spans if n == name]

    def program_seconds(self, match: str) -> dict[str, float]:
        """Per device, the seconds of every program whose name contains
        ``match``."""
        return {
            d: sum(v for k, v in progs.items() if match in k)
            for d, progs in self.programs.items()
        }

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of the window (no device busy), longest first,
        named by the innermost harness span that covers its middle."""
        busy = _union([iv for d in self.devices for iv in self.busy[d]])
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in _clip(busy, lo, hi):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        out = []
        for s, e in gaps:
            mid = (s + e) // 2
            inner = [(se - ss, n) for n, ss, se in self.spans
                     if ss <= mid < se and n != WINDOW_SPAN]
            name = min(inner)[1] if inner else WINDOW_SPAN
            out.append((name, (e - s) / 1e9))
        out.sort(key=lambda x: -x[1])
        return out

    def top_ops(self, k: int = 10) -> list[tuple[str, float]]:
        """Operations that took most device time in the window, summed
        over devices."""
        tot: dict[str, float] = {}
        for per in self.ops.values():
            for name, sec in per.items():
                tot[name] = tot.get(name, 0.0) + sec
        return sorted(tot.items(), key=lambda x: -x[1])[:k]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _is_device(plane_name: str) -> bool:
    return DEVICE_PLANE.match(plane_name) is not None


def reduce(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    spans: list[tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    lo, hi = max(win, key=lambda x: x[1] - x[0])

    def clipped(ev) -> tuple[int, int] | None:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        return (max(s, lo), min(e, hi)) if e > lo and s < hi else None

    devices, busy, programs, ops = [], {}, {}, {}
    for plane in pd.planes:
        if not _is_device(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        op_lines = [lines[OPS_LINE]] if OPS_LINE in lines else list(lines.values())
        ivs, per_op = [], {}
        for line in op_lines:
            for ev in line.events:
                iv = clipped(ev)
                if iv is not None:
                    ivs.append(iv)
                    per_op[ev.name] = per_op.get(ev.name, 0.0) + (iv[1] - iv[0]) / 1e9
        per_prog = {}
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            iv = clipped(ev)
            if iv is not None:
                per_prog[ev.name] = per_prog.get(ev.name, 0.0) + (iv[1] - iv[0]) / 1e9
        devices.append(plane.name)
        busy[plane.name] = _union(ivs)
        programs[plane.name] = per_prog
        ops[plane.name] = per_op
    return Reduced(window=(lo, hi), devices=devices, busy=busy,
                   programs=programs, ops=ops, spans=spans)
