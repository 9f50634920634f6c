"""Exploration-engine benchmark: end-to-end (characterize + sweep) wall
time for the whole suite, old serial path vs the suite-level engine.

Three front-half configurations are timed:

  * ``serial``  — the PR-1 reference: per-circuit prefix-*tree* runner (no
    structural dedup, no cache, no pool), one ``characterize`` per recipe.
  * ``cold``    — `transforms.characterize_suite` against an empty
    on-disk cache: shared-prefix DAG with structural dedup + process pool.
  * ``warm``    — the same call again: every (circuit, recipe) served from
    the `CharacterizationCache`, no transform runs at all.

The back half is timed both ways: the per-circuit scalar loop
(``backend="python"``) and the one-call suite sweep
(`explorer.explore_suite`, circuits x recipes x topologies vmapped,
riding the fused device-resident pipeline: FilterEnergy runs inside the
jitted pass and only the winners cross the host boundary).
Cross-checks that every backend picks the identical best implementation.

    PYTHONPATH=src python -m benchmarks.bench_explorer            # full: 9 circuits, 65 recipes
    PYTHONPATH=src python -m benchmarks.bench_explorer --smoke    # CI: 4 circuits, 9 recipes, no serial baseline
    PYTHONPATH=src python -m benchmarks.bench_explorer --scale default

Emits ``BENCH_explorer.json``: per-circuit and suite-total wall times for
every path plus the end-to-end speedups (``total.e2e_*``).
"""

from __future__ import annotations

import argparse
import shutil
import tempfile
import time

from repro.core import circuits as C
from repro.core.explorer import explore, explore_suite
from repro.core.transforms import (
    CharacterizationCache,
    _TRANSFORM_FNS,
    characterize_suite,
    enumerate_recipes,
)

from .common import Csv, merge_json, timeit

SMOKE_CIRCUITS = ("adder", "bar", "sqrt", "max")
SMOKE_RECIPES = 8


def characterize_prefix_tree(rtl, recipes):
    """The PR-1 front half, kept as the benchmark's reference point:
    prefix-shared transform applications (64 per circuit), one ``ChaAIG``
    per recipe — no structural dedup, no persistence, no pool."""
    cache = {(): rtl}

    def run(r):
        if r not in cache:
            cache[r] = _TRANSFORM_FNS[r[-1]](run(r[:-1]))
        return cache[r]

    return {r: run(r).characterize() for r in [()] + list(recipes)}


def run(
    csv: Csv | None = None,
    scale: str = "tiny",
    n_recipes: int | None = None,
    only=None,
    n_iter: int = 3,
    out_json: str = "BENCH_explorer.json",
    mode: str = "physical",
    baseline: bool = True,
    n_jobs: int | None = None,
    cache_dir: str | None = None,
) -> dict:
    csv = csv or Csv()
    recipes = enumerate_recipes()
    if n_recipes is not None:
        recipes = recipes[:n_recipes]
    suite = C.benchmark_suite(scale=scale, only=only)

    # ---- front half -------------------------------------------------------
    serial_s = {}
    if baseline:
        for name, rtl in suite.items():
            t0 = time.time()
            characterize_prefix_tree(rtl, [tuple(r) for r in recipes])
            serial_s[name] = time.time() - t0

    own_cache_dir = cache_dir is None
    cache_root = cache_dir or tempfile.mkdtemp(prefix="repro-cha-cache-")
    try:
        cache = CharacterizationCache(cache_root)
        t0 = time.time()
        cha = characterize_suite(suite, recipes, cache=cache, n_jobs=n_jobs)
        cold_s = time.time() - t0
        t0 = time.time()
        cha_warm = characterize_suite(suite, recipes, cache=cache, n_jobs=n_jobs)
        warm_s = time.time() - t0
        assert cha_warm == cha, "warm cache characterization drifted"
    finally:
        if own_cache_dir:
            shutil.rmtree(cache_root, ignore_errors=True)

    # ---- back half --------------------------------------------------------
    t_suite = timeit(
        lambda: explore_suite(suite, cha=cha, mode=mode, backend="jax"),
        n_warmup=1, n_iter=n_iter,
    )
    res_suite = explore_suite(suite, cha=cha, mode=mode, backend="jax")

    per_circuit = {}
    totals = dict(python_us=0.0, jax_us=0.0, implementations=0)
    for name, rtl in suite.items():
        t_py = timeit(
            lambda: explore(rtl, cha=cha[name], mode=mode, backend="python"),
            n_warmup=1, n_iter=n_iter,
        )
        t_jx = timeit(
            lambda: explore(rtl, cha=cha[name], mode=mode, backend="jax"),
            n_warmup=1, n_iter=n_iter,
        )
        res_py = explore(rtl, cha=cha[name], mode=mode, backend="python")
        res_sx = res_suite[name]
        agree = (
            res_py.best.recipe == res_sx.best.recipe
            and res_py.best.topo == res_sx.best.topo
            and abs(res_py.best.metrics.energy_nj - res_sx.best.metrics.energy_nj)
            < 1e-6
        )
        speedup = t_py / t_jx if t_jx > 0 else float("inf")
        per_circuit[name] = dict(
            gates=res_py.best.stats.total_gates,
            implementations=res_py.n_evaluations,
            characterize_serial_s=round(serial_s.get(name, 0.0), 3),
            python_us=round(t_py, 1),
            jax_us=round(t_jx, 1),
            speedup=round(speedup, 2),
            best=dict(
                topo=res_sx.best.topo.name,
                recipe=",".join(res_sx.best.recipe) or "-",
                energy_nj=res_sx.best.metrics.energy_nj,
            ),
            backends_agree=agree,
        )
        totals["python_us"] += t_py
        totals["jax_us"] += t_jx
        totals["implementations"] += res_py.n_evaluations
        csv.add(
            f"explorer/{name}", t_jx,
            f"python_us={t_py:.0f};jax_us={t_jx:.0f};"
            f"speedup={speedup:.1f}x;agree={agree}",
        )

    suite_speedup = (
        totals["python_us"] / totals["jax_us"] if totals["jax_us"] else 0.0
    )
    serial_total = sum(serial_s.values())
    suite_sweep_s = t_suite * 1e-6
    e2e = dict(
        # end-to-end = characterize + full-suite sweep, in seconds
        serial_s=round(serial_total + totals["jax_us"] * 1e-6, 3)
        if baseline else None,
        cold_s=round(cold_s + suite_sweep_s, 3),
        warm_s=round(warm_s + suite_sweep_s, 3),
    )
    if baseline and e2e["cold_s"]:
        e2e["speedup_cold"] = round(e2e["serial_s"] / e2e["cold_s"], 2)
        e2e["speedup_warm"] = round(e2e["serial_s"] / e2e["warm_s"], 2)
    out = dict(
        scale=scale,
        n_recipes=len(recipes) + 1,  # + baseline ()
        n_circuits=len(suite),
        fused_selection=True,  # explore_suite runs FilterEnergy on device
        per_circuit=per_circuit,
        total=dict(
            implementations=totals["implementations"],
            characterize_serial_s=round(serial_total, 3) if baseline else None,
            characterize_cold_s=round(cold_s, 3),
            characterize_warm_s=round(warm_s, 3),
            python_us=round(totals["python_us"], 1),
            jax_us=round(totals["jax_us"], 1),
            suite_sweep_us=round(t_suite, 1),
            speedup=round(suite_speedup, 2),
            e2e=e2e,
            all_agree=all(c["backends_agree"] for c in per_circuit.values()),
        ),
    )
    # Merge-preserving write: other benches own sibling top-level keys
    # in the same json.
    merge_json(out_json, out)
    csv.add(
        "explorer/TOTAL", totals["jax_us"],
        f"python_us={totals['python_us']:.0f};jax_us={totals['jax_us']:.0f};"
        f"speedup={suite_speedup:.1f}x;cha_cold={cold_s:.1f}s;"
        f"cha_warm={warm_s:.2f}s;json={out_json}",
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "default", "paper"], default="tiny")
    ap.add_argument("--recipes", type=int, default=None,
                    help="limit recipe count (default: all 64)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: few circuits, few recipes, 1 iter, "
                         "no serial baseline")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the serial (PR-1 reference) front half")
    ap.add_argument("--jobs", type=int, default=None,
                    help="characterization workers (default: min(4, cpus))")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent characterization cache directory "
                         "(default: fresh temp dir, deleted afterwards)")
    ap.add_argument("--out", default="BENCH_explorer.json")
    args = ap.parse_args()
    kw = dict(scale=args.scale, n_recipes=args.recipes, out_json=args.out,
              baseline=not args.no_baseline, n_jobs=args.jobs,
              cache_dir=args.cache_dir)
    if args.smoke:
        kw.update(scale="tiny", n_recipes=SMOKE_RECIPES, only=SMOKE_CIRCUITS,
                  n_iter=1, baseline=False)
    print("name,us_per_call,derived")
    run(**kw)


if __name__ == "__main__":
    main()
