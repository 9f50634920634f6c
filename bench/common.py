"""What every part of the benchmark shares: the run context, the
harness's host spans, the benchmark's own inputs (frozen data, energy
model variants), and small statistics helpers.

Nothing here imports the program under test.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gzip
import importlib.util
import json
from pathlib import Path

import numpy as np

import reference as ref


class Spans:
    """The harness's own host spans, around each call into the program.

    In a traced run each span is written into the profiler's trace
    (`jax.profiler.TraceAnnotation`), on the device events' clock, where
    the trace reduction reads it; otherwise a span costs nothing."""

    def __init__(self, traced: bool):
        self.traced = traced

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


@dataclasses.dataclass
class Check:
    """One number compared for `correct`, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Ctx:
    root: Path                # the checkout
    seed: int
    seconds: float
    traced: bool
    cell: dict                # BENCHMARK.json workload entry
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json
    work_dir: Path            # fixed scratch directory of this cell
    spans: Spans
    counters: dict = dataclasses.field(default_factory=dict)

    def rng(self, *stream: int) -> np.random.Generator:
        """A generator for one named stream of this run's seed."""
        return np.random.default_rng([self.seed, *stream])


@dataclasses.dataclass
class Window:
    """What a generator's measured window produced."""

    start_ns: int
    end_ns: int
    attempted: int
    failed: int
    end_to_end: dict          # metric name -> value
    state: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def load_json(path: Path) -> dict:
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rt") as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------


def netlists(ctx: Ctx) -> dict[str, dict]:
    """The configuration's circuits as fanin-literal dicts, in order."""
    data = load_json(ctx.root / ctx.config["netlists"])["circuits"]
    return {name: data[name] for name in ctx.config["circuits"]}


def frozen_cha(ctx: Ctx) -> dict[str, dict[tuple[str, ...], dict]]:
    """Frozen AigStats dicts of every (circuit, recipe) of the
    configuration, keyed by recipe tuple, in the configuration's recipe
    order."""
    data = load_json(ctx.root / ctx.config["frozen_cha"])
    recipes = ref.recipes(ctx.config["recipes"])
    out = {}
    for name in ctx.config["circuits"]:
        rows = data["circuits"][name]
        out[name] = {r: rows[",".join(r)] for r in recipes}
    return out


def nominal_model(config: dict) -> dict:
    """The configuration's nominal energy model as one-row arrays."""
    m = config["energy_model"]
    return {
        f: np.asarray([m[f]], dtype=np.float64) for f in ref.MODEL_FIELDS
    }


def monte_carlo(config: dict, rng: np.random.Generator, n: int,
                sigma: float) -> dict:
    """``n`` energy-model variants: row 0 nominal, every other row
    scales each varied field (each op type of a per-op field on its own)
    by ``max(N(1, sigma), floor)``, with the configured caps.  Returns
    every field as a (V,) array, per-op fields (V, 3)."""
    base = config["energy_model"]
    var = config["variation"]
    out = {}
    for f in ref.MODEL_FIELDS:
        nominal = np.asarray(base[f], dtype=np.float64)
        rows = np.broadcast_to(nominal, (n,) + nominal.shape).copy()
        if f in var["fields"] and n > 1:
            k = np.maximum(rng.normal(1.0, sigma, (n - 1,) + nominal.shape),
                           var["floor"])
            rows[1:] = nominal * k
            if f in var["caps"]:
                rows[1:] = np.minimum(rows[1:], var["caps"][f])
        out[f] = rows
    return out


def idle_pct(m: dict) -> float | None:
    """Share of a traced window in which no operation ran on the device,
    averaged over the chips used (the per-cell ``device_idle_pct.*``
    readers)."""
    red = m["trace"]
    if red.window_s <= 0 or not red.devices:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation) of ``values``."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def rel_err(got: float, want: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / abs(want)
