"""The program's own spans in a traced run.

`repro.runtime.trace` records one ``rcim.*`` host event per layer
boundary in the profiler's ``.xplane.pb``, on the clock of the device
events that `trace_reduce` reads.  This module reads them from the same
file, clipped to the same window (``bench.window``), and gives:

* every span in the window: its name, interval, arguments, the host line
  it ran on, its self time (its duration minus the union of its child
  spans on that line) and the device-busy time inside it;
* `ProgramSpans.idle_by_span`: every device-idle interval of the window,
  cut where the innermost program span covering it changes, summed by
  that span's name; idle time outside any program span is ``(none)``.

A run whose program records no spans reads as no spans: `load` returns
None, and so does every reader built on it.  Nothing here imports the
program under test.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import trace_reduce

PREFIX = "rcim."
#: The name `ProgramSpans.idle_by_span` gives idle time outside any span.
NONE = "(none)"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                # ns on the trace clock, clipped to the window
    end: int
    line: tuple[str, int]     # (host plane, line index): one thread
    args: dict
    depth: int                # enclosing program spans on the same line
    self_ns: int              # end - start minus the union of its children

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@functools.lru_cache(maxsize=2)
def _parse(path: str, _mtime_ns: int) -> tuple[tuple, ...]:
    """Every ``rcim.*`` host event of one file, unclipped, with its
    depth and parent on its line: (name, start, end, line, args, depth,
    parent index or -1)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    raw = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    raw.append((ev.name, s, s + int(ev.duration_ns), (plane.name, li),
                                dict(ev.stats)))
    # Spans of one thread nest: sorted by (line, start, longest first),
    # each span's parent is the innermost open span that contains it.
    raw.sort(key=lambda r: (r[3], r[1], -r[2]))
    out, stack = [], []
    for i, (name, s, e, line, args) in enumerate(raw):
        while stack and (raw[stack[-1]][3] != line or raw[stack[-1]][2] < e):
            stack.pop()
        out.append((name, s, e, line, args, len(stack), stack[-1] if stack else -1))
        stack.append(i)
    return tuple(out)


class ProgramSpans:
    """The program spans of one traced window (`load`)."""

    def __init__(self, raw: tuple, red: trace_reduce.Reduced):
        self.red = red
        lo, hi = red.window
        children: dict[int, list[tuple[int, int]]] = {}
        for name, s, e, line, args, depth, parent in raw:
            if parent >= 0:
                children.setdefault(parent, []).append((s, e))
        self.spans = []
        for i, (name, s, e, line, args, depth, _parent) in enumerate(raw):
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            inner = trace_reduce._covered(trace_reduce._union(children.get(i, [])), cs, ce)
            self.spans.append(Span(name, cs, ce, line, args, depth, ce - cs - inner))

    def named(self, name: str) -> list[Span]:
        return [sp for sp in self.spans if sp.name == name]

    def prefixed(self, prefix: str) -> list[Span]:
        return [sp for sp in self.spans if sp.name.startswith(prefix)]

    def seconds(self, name: str) -> float:
        return sum(sp.seconds for sp in self.named(name))

    def busy_s(self, sp: Span) -> float:
        """Device-busy seconds inside the span (mean over devices)."""
        return self.red.busy_within(sp.start, sp.end)

    def covered_s(self, outer: Span, prefix: str) -> float:
        """Seconds of ``outer`` inside spans named ``prefix...`` on its
        line."""
        ivs = [(sp.start, sp.end) for sp in self.spans
               if sp.line == outer.line and sp.name.startswith(prefix)
               and sp is not outer]
        return trace_reduce._covered(trace_reduce._union(ivs), outer.start, outer.end) / 1e9

    def _innermost(self) -> list[tuple[int, int, str]]:
        """The window cut into segments, each named by the innermost
        span covering it (deepest, then latest started); uncovered time
        is left out."""
        spans = self.spans
        bounds = sorted({t for sp in spans for t in (sp.start, sp.end)})
        by_start = sorted(range(len(spans)), key=lambda i: spans[i].start)
        active: set[int] = set()
        segs: list[list] = []  # [start, end, span index]
        k = 0
        for a, b in zip(bounds, bounds[1:]):
            while k < len(by_start) and spans[by_start[k]].start <= a:
                active.add(by_start[k])
                k += 1
            active = {i for i in active if spans[i].end > a}
            if not active:
                continue
            i = max(active, key=lambda i: (spans[i].depth, spans[i].start))
            if segs and segs[-1][2] == i and segs[-1][1] == a:
                segs[-1][1] = b
            else:
                segs.append([a, b, i])
        return [(a, b, spans[i].name) for a, b, i in segs]

    def idle_pieces(self) -> list[tuple[str, int, int]]:
        """Every device-idle interval of the window cut at innermost-span
        boundaries: (span name or `NONE`, start, end), in time order."""
        red = self.red
        lo, hi = red.window
        busy = trace_reduce._clip(
            trace_reduce._union([iv for d in red.devices for iv in red.busy[d]]), lo, hi)
        idle, cur = [], lo
        for s, e in busy:
            if s > cur:
                idle.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            idle.append((cur, hi))
        segs = self._innermost()
        out, j = [], 0
        for s, e in idle:
            t = s
            while j < len(segs) and segs[j][1] <= t:
                j += 1
            k = j
            while t < e:
                if k < len(segs) and segs[k][0] < e:
                    a, b, name = segs[k]
                    if a > t:
                        out.append((NONE, t, a))
                        t = a
                    end = min(b, e)
                    out.append((name, t, end))
                    t = end
                    k += 1
                else:
                    out.append((NONE, t, e))
                    t = e
        return out

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of the window by innermost program span, largest
        first."""
        tot: dict[str, float] = {}
        for name, s, e in self.idle_pieces():
            tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        return dict(sorted(tot.items(), key=lambda x: -x[1]))


def load_dir(trace_dir: str, red: trace_reduce.Reduced) -> ProgramSpans | None:
    """The program spans of the newest trace under ``trace_dir``, in the
    window of its reduction ``red``; None where it holds none."""
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    ps = ProgramSpans(_parse(path, os.stat(path).st_mtime_ns), red)
    return ps if ps.spans else None


def load(m: dict) -> ProgramSpans | None:
    """`load_dir` for a per-layer reader: its cell's trace is under
    ``<cell work dir>/trace``."""
    return load_dir(str(m["ctx"].work_dir / "trace"), m["trace"])


def main(argv=None) -> int:
    """Print where a traced run's time went, as JSON: seconds in the
    window by span name (the harness's and the program's), idle seconds
    by innermost program span, and the longest idle pieces."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__.split(",")[0])
    ap.add_argument("trace_dir", help="e.g. bench/.work/<cell>/trace")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    red = trace_reduce.reduce(args.trace_dir)
    ps = load_dir(args.trace_dir, red)
    spans = [(n, s, e) for n, s, e in red.spans]
    spans += [(sp.name, sp.start, sp.end) for sp in ps.spans] if ps else []
    totals: dict[str, float] = {}
    for n, s, e in spans:
        covered = trace_reduce._covered([(s, e)], *red.window)
        totals[n] = totals.get(n, 0.0) + covered / 1e9
    pieces = sorted(ps.idle_pieces() if ps else [], key=lambda p: p[1] - p[2])
    print(json.dumps(dict(
        window_s=red.window_s, busy_s=red.busy_s(),
        span_s=dict(sorted(totals.items())),
        idle_by_span=ps.idle_by_span() if ps else {},
        longest_idle=[[n, (e - s) / 1e9] for n, s, e in pieces[:args.top]],
    ), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
