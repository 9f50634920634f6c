#!/usr/bin/env python3
"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  The cell's entry names its configuration
(``bench/configs/<config>.json``, through the ``configs`` entry's
``file``) and its traffic mix (``bench/traffic/<traffic>.json``); the mix
names the general generator that runs it (``bench/generators/<generator>.py``);
each per-layer metric is read by ``bench/metrics/<metric>.py``.

A run: refuse any platform but a TPU with as many chips as the cell
asks for; set up (inputs from ``--seed``, warm-up of every shape the
traffic uses); measure for ``--seconds``; with ``--trace 1`` record a
profiler trace of the window and reduce it; then check what the window
produced against the plain reference (`reference`).  The last line of
stdout is the result as one JSON object; the numbers compared for
``correct`` are the last lines of stderr and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import common  # noqa: E402

#: Entries of each list in a traced run's ``breakdown``.
BREAKDOWN_ENTRIES = 10


class RunError(Exception):
    """A run that cannot measure: no result line, a non-zero exit."""

    def __init__(self, message: str, code: int):
        super().__init__(message)
        self.code = code


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _entry(items: list[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise RunError(f"no {what} named {name!r} in BENCHMARK.json", 2)


def cell_metrics(spec: dict, kind: str, cell: str) -> list[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") that the cell
    reports: those listing it, and those that list no cells."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def _jax_env(root: Path) -> None:
    """The program's compile cache at a fixed path inside the checkout,
    and the TPU runtime's logs inside it too (set before jax is
    imported, so they override any outside setting)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = str(root / "bench" / ".work" / "tpu_logs")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    src = root / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"the program is not in this checkout ({src}/repro)", 2)
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def _devices(root: Path, chips: int, require_tpu: bool):
    """The devices the run uses: a TPU whose kind `bench/peaks.json`
    knows, with as many chips as the cell asks for."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu:
        if platform != "tpu":
            raise RunError(f"needs a TPU, JAX found platform {platform!r}", 3)
        if len(devs) < chips:
            raise RunError(f"the cell needs {chips} chips, JAX found {len(devs)}", 3)
        peaks = common.load_json(root / "bench" / "peaks.json")
        if devs[0].device_kind not in peaks:
            raise RunError(f"device kind {devs[0].device_kind!r} is not in "
                           f"bench/peaks.json", 3)
    return devs


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Compiles:
    """XLA programs JAX asked for while active: requests (a compile or a
    compile-cache load each) and the seconds spent in the backend."""

    def __init__(self):
        import jax

        self.active = False
        self.requests = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if self.active and name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def summary(self) -> dict:
        return {"requests": self.requests, "seconds": self.seconds}


class HostStats:
    """What the host did during the window, beside the wall clock: the
    garbage collector's passes and pauses per generation, the process's
    CPU seconds, and the machine's CPU seconds stolen by its hypervisor
    (from ``/proc/stat``, where there is one)."""

    def __init__(self):
        self.gc = {g: [0, 0.0] for g in range(3)}
        self._t = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            g = self.gc[info["generation"]]
            g[0] += 1
            g[1] += time.perf_counter() - self._t
            self._t = None

    @staticmethod
    def _steal_s() -> float | None:
        try:
            with open("/proc/stat") as f:
                cpu = f.readline().split()
            return int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return None

    def start(self) -> None:
        self.cpu0, self.steal0 = time.process_time(), self._steal_s()
        self.gc = {g: [0, 0.0] for g in range(3)}

    def summary(self) -> dict:
        gc.callbacks.remove(self._gc)
        steal = self._steal_s()
        return {
            "process_cpu_s": time.process_time() - self.cpu0,
            "steal_s": None if steal is None or self.steal0 is None
            else steal - self.steal0,
            "gc": {f"gen{g}": {"passes": n, "seconds": s}
                   for g, (n, s) in self.gc.items()},
        }


def _trace_counts() -> dict:
    from repro.analysis.registry import trace_counts

    return dict(trace_counts())


def prepare(root: Path, workload: str | dict, seed: int, seconds: float, traced: bool):
    """The cell's spec entries, its run context and its generator module.

    ``workload`` names a cell of ``BENCHMARK.json``, or is a cell entry
    of its own: a mix that has no cell yet (the service's, PERF.md)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = (workload if isinstance(workload, dict)
            else _entry(spec["workloads"], workload, "workload"))
    conf_entry = _entry(spec["configs"], cell["config"], "config")
    config = common.load_json(root / conf_entry["file"])
    traffic = common.load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    generator = common.load_module(
        root / "bench" / "generators" / f"{traffic['generator']}.py",
        f"bench_generator_{traffic['generator']}",
    )
    work_dir = root / "bench" / ".work" / cell["name"]
    work_dir.mkdir(parents=True, exist_ok=True)
    ctx = common.Ctx(
        root=root, seed=seed, seconds=seconds, traced=traced,
        cell=cell, config=config, traffic=traffic, work_dir=work_dir,
        spans=common.Spans(traced),
    )
    return spec, ctx, generator


def run(argv=None, root: Path | None = None, require_tpu: bool = True) -> dict:
    """One run; returns the result object (raises `RunError`)."""
    args = parse(argv)
    root = Path(root) if root is not None else BENCH.parent
    spec, ctx, generator = prepare(root, args.workload, args.seed, args.seconds,
                                bool(args.trace))
    if ctx.traced:
        # A mix whose trace grows fast traces a shorter window of its own.
        ctx.seconds = min(ctx.seconds, ctx.traffic.get("trace_seconds", ctx.seconds))
    cell, traffic = ctx.cell, ctx.traffic
    _jax_env(root)
    devs = _devices(root, cell["chips"], require_tpu)
    import jax

    state = generator.setup(ctx)
    compiles = Compiles()
    host = HostStats()
    compiles.active = True
    host.start()
    trace_dir = ctx.work_dir / "trace"
    if ctx.traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    counts0 = _trace_counts()
    setup_s = time.perf_counter() - T_START
    try:
        with ctx.spans.span("bench.window"):
            win = generator.window(ctx, state)
    finally:
        compiles.active = False
        if ctx.traced:
            jax.profiler.stop_trace()
    ctx.counters["compiles"] = compiles.summary()
    ctx.counters["host"] = host.summary()
    counts1 = _trace_counts()
    ctx.counters["traces"] = {k: v - counts0.get(k, 0) for k, v in counts1.items()
                              if v != counts0.get(k, 0)}
    memory_peak = _memory_peak(devs)

    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": memory_peak,
    }
    metrics, breakdown = {}, None
    if ctx.traced:
        import trace_reduce

        red = trace_reduce.reduce(str(trace_dir))
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        m_in = dict(trace=red, counters=ctx.counters, window=win, ctx=ctx)
        for m in cell_metrics(spec, "per_layer", cell["name"]):
            reader = common.load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                                        "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(m_in)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {
            "device_ops": [[n, s] for n, s in red.top_ops(BREAKDOWN_ENTRIES)],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps()[:BREAKDOWN_ENTRIES]],
        }
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        for m in cell_metrics(spec, "end_to_end", cell["name"]):
            if m["name"] not in values:
                raise RunError(f"generator {traffic['generator']!r} gives no {m['name']}", 1)
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = generator.check(ctx, state, win)
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["counters"] = {k: v for k, v in ctx.counters.items()
                          if isinstance(v, (int, float, dict))}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except RunError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return e.code
    except Exception:  # noqa: BLE001 — a failed run prints no result line
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
