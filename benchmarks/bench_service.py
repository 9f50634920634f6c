"""Exploration-service stress bench: cold vs warm latency, throughput,
trace accounting, and per-request winner agreement with the offline path.

Phases:

  1. **cold** — first request per circuit: pays characterization + (for
     a new bucket shape) jit compilation.
  2. **warm throughput** — a burst of mixed-constraint requests over the
     now-cached fingerprints, submitted all at once (continuous
     batching): requests/sec.
  3. **warm latency** — sequential submits (one in flight at a time):
     end-to-end p50/p99 per request.  Asserted ``<< cold p50``.
  4. **re-rank** — constraint-only changes over a cached grid: asserted
     to add **zero** new jit traces of any kernel.
  5. **agreement** — every response's winner replayed against a fresh
     offline `explore_request`: topology + recipe identical, energy
     bit-identical to the offline device grid cell.

Trace accounting: the fused suite kernel must have traced exactly once
per distinct bucket shape the service reports — repeat shapes reuse the
compiled sweep.

    PYTHONPATH=src python -m benchmarks.bench_service           # full
    PYTHONPATH=src python -m benchmarks.bench_service --smoke   # CI

Merges a ``"service"`` section into ``BENCH_explorer.json``.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from .common import Csv, merge_json

SMOKE_CIRCUITS = ("adder", "bar", "sqrt", "max")
SMOKE_RECIPES = 8


def _percentiles(ms: list) -> tuple[float, float]:
    return float(np.percentile(ms, 50)), float(np.percentile(ms, 99))


def run_service_bench(
    csv: Csv | None = None,
    scale: str = "tiny",
    only=None,
    n_recipes: int | None = None,
    n_requests: int = 32,
    n_variants: int = 8,
    out_json: str = "BENCH_explorer.json",
    cache_dir: str | None = None,
    merge_key: str = "service",
) -> dict:
    from repro.core import batch as B
    from repro.core.circuits import benchmark_suite
    from repro.core.explorer import explore_request
    from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable
    from repro.core.transforms import enumerate_recipes
    from repro.serve.explore_service import (
        ExplorationService,
        ExploreRequest,
    )

    topos = TOPOLOGY_LIBRARY
    recipes = enumerate_recipes()
    if n_recipes is not None:
        recipes = recipes[:n_recipes]
    circuits = list(benchmark_suite(scale=scale, only=only).values())
    sweep = ModelTable.monte_carlo(n=n_variants, seed=0)
    kb_mid = sorted(t.total_kb for t in topos)[len(topos) // 2]
    constraint_mix = [
        dict(),
        dict(max_latency_ns=1e4),
        dict(max_memory_kb=kb_mid),
        dict(max_memory_kb=kb_mid, max_latency_ns=1e4),
    ]

    svc = ExplorationService(
        sram_list=topos, recipes=recipes, cache=cache_dir, max_batch=8
    )
    responses = []
    try:
        # -- phase 1: cold -------------------------------------------------
        traces0 = B.trace_counts()
        cold_ms = []
        for c in circuits:
            t0 = time.perf_counter()
            r = svc.explore(ExploreRequest(c))
            cold_ms.append((time.perf_counter() - t0) * 1e3)
            assert r.ok, r.error
            responses.append(r)
        # one cold sweep request (its own (V>1) bucket + model grid)
        t0 = time.perf_counter()
        r = svc.explore(ExploreRequest(circuits[0], model_sweep=sweep))
        cold_sweep_ms = (time.perf_counter() - t0) * 1e3
        assert r.ok, r.error
        responses.append(r)

        # -- phase 2: warm throughput (burst) ------------------------------
        # sweep requests reuse circuits[0]'s warmed (fingerprint, model)
        # grid; every other combination was warmed in the cold phase too
        burst = [
            ExploreRequest(
                circuits[0] if i % 5 == 4 else circuits[i % len(circuits)],
                model_sweep=sweep if i % 5 == 4 else None,
                **constraint_mix[i % len(constraint_mix)],
            )
            for i in range(n_requests)
        ]
        t0 = time.perf_counter()
        rs = [f.result() for f in svc.submit_batch(burst)]
        burst_s = time.perf_counter() - t0
        assert all(r.ok for r in rs), [r.error for r in rs if not r.ok]
        assert all(r.cha_cache_hit and r.grid_cache_hit for r in rs)
        responses.extend(rs)
        rps = n_requests / burst_s

        # -- phase 3: warm latency (sequential) ----------------------------
        warm_ms = []
        for i in range(min(n_requests, 16)):
            req = ExploreRequest(
                circuits[i % len(circuits)],
                **constraint_mix[i % len(constraint_mix)],
            )
            t0 = time.perf_counter()
            r = svc.explore(req)
            warm_ms.append((time.perf_counter() - t0) * 1e3)
            assert r.ok and r.grid_cache_hit
            responses.append(r)

        # -- phase 4: re-rank-only constraint changes ----------------------
        traces_rerank = B.trace_counts()
        for kw in constraint_mix[1:] + [dict(max_latency_ns=123.0)]:
            r = svc.explore(ExploreRequest(circuits[0], **kw))
            assert r.ok and r.grid_cache_hit
            responses.append(r)
        rerank_retrace = sum(B.trace_counts().values()) - sum(
            traces_rerank.values()
        )
        assert rerank_retrace == 0, (
            f"constraint re-ranks recompiled {rerank_retrace} kernels"
        )

        # -- trace accounting: one fused trace per bucket shape ------------
        stats = svc.stats()
        fused_traces = B.trace_counts().get("fused_suite", 0) - traces0.get(
            "fused_suite", 0
        )
        assert fused_traces == stats["distinct_buckets"], (
            f"{fused_traces} fused traces for "
            f"{stats['distinct_buckets']} bucket shapes"
        )
        assert stats["batches"] >= stats["distinct_buckets"]
    finally:
        svc.close()

    # -- phase 5: winner agreement with the offline path -------------------
    # (after the service run so the offline calls' own jit traces cannot
    # pollute the accounting above)
    offline_cache: dict = {}
    n_agree = 0
    for r in responses:
        key = (
            r.fingerprint,
            r.request.max_memory_kb,
            r.request.max_latency_ns,
            r.request.model_sweep is not None,
        )
        if key not in offline_cache:
            offline_cache[key] = explore_request(
                r.request.circuit,
                topos,
                recipes,
                max_memory_kb=r.request.max_memory_kb,
                max_latency_ns=r.request.max_latency_ns,
                model_sweep=r.request.model_sweep,
            )
        off = offline_cache[key]
        assert r.winner.topology.name == off.best.topo.name, (
            r.request.circuit.name, r.winner.topology.name, off.best.topo.name
        )
        assert r.winner.recipe == tuple(off.best.recipe)
        ti = off.grid.topologies.index(off.best.topo)
        ri = off.grid.recipes.index(tuple(off.best.recipe))
        assert r.winner.energy_nj == off.grid.cell(ti, ri).energy_nj
        n_agree += 1

    cold_p50, _ = _percentiles(cold_ms)
    warm_p50, warm_p99 = _percentiles(warm_ms)
    assert warm_p50 < cold_p50 / 10, (
        f"warm p50 {warm_p50:.1f} ms not << cold p50 {cold_p50:.1f} ms"
    )

    summary = {
        "scale": scale,
        "n_circuits": len(circuits),
        "n_recipes": len(recipes),
        "n_requests_total": len(responses),
        "cold_p50_ms": round(cold_p50, 3),
        "cold_sweep_ms": round(cold_sweep_ms, 3),
        "warm_p50_ms": round(warm_p50, 3),
        "warm_p99_ms": round(warm_p99, 3),
        "burst_rps": round(rps, 2),
        "rerank_retrace": rerank_retrace,
        "fused_traces": fused_traces,
        "distinct_buckets": stats["distinct_buckets"],
        "winners_agree": n_agree,
        "cha_hits": stats.get("cha_hits", 0),
        "grid_hits": stats.get("grid_hits", 0),
    }
    if csv is not None:
        csv.add("service/cold_p50", cold_p50 * 1e3,
                f"first-request latency ({len(circuits)} circuits)")
        csv.add("service/warm_p50", warm_p50 * 1e3,
                f"p99={warm_p99:.1f}ms")
        csv.add("service/burst", burst_s * 1e6 / n_requests,
                f"rps={rps:.1f}")
        csv.add("service/traces", 0.0,
                f"fused={fused_traces};buckets={stats['distinct_buckets']};"
                f"rerank_retrace={rerank_retrace}")
        csv.add("service/agreement", 0.0,
                f"winners_agree={n_agree}/{len(responses)}")
    merge_json(out_json, {merge_key: summary})
    print(f"service bench: {summary}")
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--out", default="BENCH_explorer.json")
    ap.add_argument("--cache", default=None)
    args = ap.parse_args()

    csv = Csv()
    kw: dict = dict(out_json=args.out, cache_dir=args.cache)
    if args.smoke:
        kw.update(scale="tiny", only=SMOKE_CIRCUITS,
                  n_recipes=SMOKE_RECIPES, n_requests=16, n_variants=4)
    if args.requests is not None:
        kw["n_requests"] = args.requests
    run_service_bench(csv, **kw)
    csv.save("bench_service.csv")


if __name__ == "__main__":
    main()
