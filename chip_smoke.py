#!/usr/bin/env python3
"""Bring-up smoke test: the exploration pipeline on one TPU chip.

Drives the main path once, in this one process, through the entry points
a user calls, on the paper's deployment shape: the 9-circuit
default-scale suite x all 65 recipes x the 12 library topologies.

1. Cold characterization on the device backend (`kernels/aig_sim`, the
   Pallas engine wherever ``"auto"`` picks it), checked recipe by recipe
   against the python backend on `PARITY_CIRCUITS`.
2. The fused float64 Monte-Carlo sweep: 9 circuits x 16 variants x 12
   topologies x 65 recipes = 112,320 designs in one device call.  Every
   (circuit, variant) winner is checked against the scalar
   ``explore(..., backend="python")`` reference, and winner energies
   against it to `ENERGY_RTOL`.
3. An `ExplorationService` answering `N_REQUESTS` requests round-robin
   over the circuits (plain, memory-budget, latency-bound and 8-variant
   Monte-Carlo queries); each must be ok, not degraded, and equal to the
   offline `explore_request` answer.

Phase times printed here are smoke timings of one cold run, not
metrics.  Exits non-zero, with no result line, when JAX finds no TPU or
any check fails; otherwise the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Usage:  python3 chip_smoke.py
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RUN_DIR = os.path.join(ROOT, "runs", "chip_smoke")
PARITY_CIRCUITS = ("bar", "max")
ENERGY_RTOL = 1e-9
N_REQUESTS = 18
N_VARIANTS = 16


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def phase(name: str, t0: float, counts0: dict) -> dict:
    from repro.analysis.registry import trace_counts

    counts = trace_counts()
    new = {k: v - counts0.get(k, 0) for k, v in counts.items()
           if v != counts0.get(k, 0)}
    print(f"[smoke timing] {name}: {time.perf_counter() - t0:.3f} s; "
          f"compiles {json.dumps(new, sort_keys=True)}", flush=True)
    return counts


def characterize(suite, recipes):
    from repro.core.transforms import characterize_suite

    cache = os.path.join(RUN_DIR, "cha")
    shutil.rmtree(cache, ignore_errors=True)
    return characterize_suite(
        suite, recipes, cache=cache, n_jobs=1, backend="device"
    ), cache


def check_characterization(suite, recipes, cha) -> None:
    from repro.core.transforms import characterize_suite
    from repro.kernels import aig_sim

    engines = {
        name: aig_sim._resolve_engine("auto", aig_sim.compile_aig(aig))
        for name, aig in suite.items()
    }
    print(f"aig_sim engine per circuit: {engines}", flush=True)
    if "pallas" in engines.values():
        check(aig_sim.trace_counts().get("aig_eval_pallas", 0) > 0,
              "the Pallas engine was picked but never ran")
    ref = characterize_suite(
        {n: suite[n] for n in PARITY_CIRCUITS}, recipes, n_jobs=1,
        backend="python",
    )
    for name in PARITY_CIRCUITS:
        check(len(ref[name]) == len(recipes) + 1 == len(cha[name]),
              f"{name}: recipe count")
        bad = [r for r in ref[name] if ref[name][r] != cha[name][r]]
        check(not bad, f"{name}: device AigStats differ from python on "
                       f"{len(bad)} recipes, e.g. {bad[:3]}")
    print(f"characterization parity: {PARITY_CIRCUITS} x {len(recipes) + 1} "
          f"recipes identical to backend='python'", flush=True)


def winner_key(recipe, topo) -> tuple:
    return tuple(recipe), topo.name


def check_sweep(suite, recipes, cha, table, res) -> None:
    from repro.core.explorer import explore
    from repro.core.sram import TOPOLOGY_LIBRARY

    worst = 0.0
    for name, aig in suite.items():
        var = res[name].variation
        check(var is not None and var.n_variants == len(table),
              f"{name}: variation result")
        for v in range(len(table)):
            ref = explore(
                aig, TOPOLOGY_LIBRARY, recipes, model=table.model(v),
                backend="python", cha=cha[name],
            )
            got = winner_key(*var.winners[v])
            want = winner_key(ref.best.recipe, ref.best.topo)
            check(got == want, f"{name} variant {v}: winner {got} != "
                               f"python reference {want}")
            e_ref = ref.best.metrics.energy_nj
            rel = abs(float(var.winner_energy_nj[v]) - e_ref) / abs(e_ref)
            worst = max(worst, rel)
            check(rel <= ENERGY_RTOL, f"{name} variant {v}: winner energy "
                                      f"rel diff {rel:.3e} > {ENERGY_RTOL}")
    print(f"sweep parity: {len(suite)} x {len(table)} winners identical to "
          f"the python reference; winner energy max rel diff {worst:.3e} "
          f"(tolerance {ENERGY_RTOL})", flush=True)


def run_service(suite, recipes, cha, cache) -> None:
    from repro.core.explorer import explore_request
    from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable
    from repro.serve.explore_service import ExplorationService, ExploreRequest

    names = list(suite)
    kb_mid = sorted(t.total_kb for t in TOPOLOGY_LIBRARY)[
        len(TOPOLOGY_LIBRARY) // 2
    ]
    mc8 = ModelTable.monte_carlo(n=8, sigma=0.1, seed=1)
    kinds = [
        ("plain", dict()),
        ("memory", dict(max_memory_kb=kb_mid)),
        ("latency", dict(max_latency_ns=1e4)),
        ("mc8", dict(model_sweep=mc8)),
    ]
    reqs, keys = [], []
    for i in range(N_REQUESTS):
        kind, kw = kinds[i % len(kinds)]
        keys.append(names[i % len(names)])
        reqs.append(ExploreRequest(
            circuit=suite[keys[-1]], tag=f"{kind}-{i}", **kw
        ))
    with ExplorationService(
        sram_list=TOPOLOGY_LIBRARY, recipes=recipes, cache=cache,
        cha_backend="device",
    ) as svc:
        resps = [f.result() for f in svc.submit_batch(reqs)]
        stats = svc.stats()
    for name, req, resp in zip(keys, reqs, resps):
        check(resp.ok, f"{req.tag}: {resp.error}")
        check(not resp.degraded, f"{req.tag}: served degraded")
        off = explore_request(
            req.circuit, TOPOLOGY_LIBRARY, recipes, cha=cha[name],
            max_memory_kb=req.max_memory_kb,
            max_latency_ns=req.max_latency_ns, model_sweep=req.model_sweep,
        )
        got = winner_key(resp.winner.recipe, resp.winner.topology)
        want = winner_key(off.best.recipe, off.best.topo)
        check(got == want, f"{req.tag}: service winner {got} != offline "
                           f"{want}")
        if req.model_sweep is not None:
            check([winner_key(*w) for w in resp.variation.winners]
                  == [winner_key(*w) for w in off.variation.winners],
                  f"{req.tag}: per-variant winners differ from offline")
    check(stats.get("degraded", 0) == 0, f"service stats: {stats}")
    print(f"service: {len(resps)} requests ok, none degraded, winners equal "
          f"the offline explore_request", flush=True)


def one_chip(suite, recipes) -> None:
    from repro.core.explorer import explore_suite
    from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable

    counts = {}
    t0 = time.perf_counter()
    cha, cache = characterize(suite, recipes)
    counts = phase("cold characterization (device backend)", t0, counts)
    check_characterization(suite, recipes, cha)

    table = ModelTable.monte_carlo(n=N_VARIANTS, sigma=0.1, seed=0)
    n_designs = len(suite) * len(table) * len(TOPOLOGY_LIBRARY) * (
        len(recipes) + 1
    )
    t0 = time.perf_counter()
    res = explore_suite(suite, TOPOLOGY_LIBRARY, recipes, cha=cha,
                        model_sweep=table)
    counts = phase(f"fused f64 Monte-Carlo sweep ({n_designs} designs)",
                   t0, counts)
    check_sweep(suite, recipes, cha, table, res)

    t0 = time.perf_counter()
    run_service(suite, recipes, cha, cache)
    phase(f"service ({N_REQUESTS} requests + offline references)", t0,
          counts)


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n")[0]).parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime import jax_env
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    jax_env.setup()
    print(f"jax {jax.__version__} devices: {devices}", flush=True)
    print(f"compile cache: {jax_env.cache_dir()}", flush=True)

    from repro.core.circuits import benchmark_suite
    from repro.core.transforms import enumerate_recipes

    suite = benchmark_suite(scale="default")
    recipes = enumerate_recipes()
    os.makedirs(RUN_DIR, exist_ok=True)
    try:
        one_chip(suite, recipes)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
