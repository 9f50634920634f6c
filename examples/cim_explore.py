"""Paper reproduction driver — Algorithm I over the EPFL-like suite.

    PYTHONPATH=src python examples/cim_explore.py --circuit adder --scale tiny
    PYTHONPATH=src python examples/cim_explore.py --all --scale default  # slower

    # persistent characterization cache: first run is cold, reruns are
    # near-instant (the sweep itself is one vmapped device call)
    PYTHONPATH=src python examples/cim_explore.py --all --cache runs/cha_cache

    # energy-model variation: sweep process corners / Monte-Carlo samples
    # through the same single compile and report a yield summary
    PYTHONPATH=src python examples/cim_explore.py --all --model-sweep mc \
        --model-variants 32

Prints the Table-I-style row for each circuit plus the best/worst spread.
"""

import argparse

from repro.core import circuits as C
from repro.core.explorer import best_worst, explore_suite
from repro.core.sram import TOPOLOGY_LIBRARY, EnergyModel, ModelTable


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--circuit", default="adder",
                    choices=list(C._GENERATORS) + ["all"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--scale", choices=["tiny", "default", "paper"], default="tiny")
    ap.add_argument("--max-latency-ns", type=float, default=None)
    ap.add_argument("--backend", choices=["python", "jax"], default="jax",
                    help="sweep backend: scalar reference or batched grid")
    ap.add_argument("--cache", default=None, metavar="DIR",
                    help="persistent characterization cache directory")
    ap.add_argument("--jobs", type=int, default=None,
                    help="characterization workers of the python backend "
                         "(default: min(4, cpus)); the device backend "
                         "always runs in this process")
    ap.add_argument("--model-sweep",
                    choices=["corners", "sensitivity", "mc", "correlated"],
                    default=None,
                    help="sweep EnergyModel variants (process corners, "
                         "one-at-a-time sensitivity, Monte-Carlo, or "
                         "correlated per-macro-geometry Monte-Carlo) through "
                         "the same compile and report a yield summary")
    ap.add_argument("--model-variants", type=int, default=16,
                    help="Monte-Carlo sample count "
                         "(--model-sweep mc/correlated)")
    ap.add_argument("--model-sigma", type=float, default=0.05,
                    help="relative sigma/spread for the model sweep")
    args = ap.parse_args()

    model_sweep = None
    if args.model_sweep == "corners":
        model_sweep = ModelTable.corners(EnergyModel(), spread=args.model_sigma)
    elif args.model_sweep == "sensitivity":
        model_sweep = ModelTable.sensitivity(EnergyModel(), rel=args.model_sigma)
    elif args.model_sweep == "mc":
        model_sweep = ModelTable.monte_carlo(
            EnergyModel(), n=args.model_variants, sigma=args.model_sigma, seed=0
        )
    elif args.model_sweep == "correlated":
        # topology-dependent (V, T) variation keyed on the library's
        # macro geometries — must match the swept topology list
        model_sweep = ModelTable.bitcell_sigma_per_macro(
            TOPOLOGY_LIBRARY, n=args.model_variants,
            sigma=args.model_sigma, seed=0,
        )

    names = list(C._GENERATORS) if (args.all or args.circuit == "all") else [args.circuit]
    suite = C.benchmark_suite(scale=args.scale, only=names)
    results = explore_suite(
        suite, max_latency_ns=args.max_latency_ns, backend=args.backend,
        cache=args.cache, n_jobs=args.jobs, model_sweep=model_sweep,
    )
    for name, res in results.items():
        rtl = suite[name]
        b, w = best_worst(res)
        row = res.table_row()
        print(f"\n=== {name} ({rtl.n_ands} AIG nodes, {res.n_recipes} recipes, "
              f"{res.n_evaluations} implementations, {res.wall_s:.1f}s) ===")
        for k, v in row.items():
            print(f"  {k:14s} {v}")
        saving = 100 * (1 - b.metrics.energy_nj / w.metrics.energy_nj)
        print(f"  best-vs-worst energy saving: {saving:.1f}% "
              f"(paper avg 89.12%)")
        if res.variation is not None:
            var = res.variation
            print(f"  model sweep ({var.n_variants} variants): "
                  f"best_yield={var.best_yield:.2f} "
                  f"latency_yield={var.latency_yield:.2f}")
            q = var.energy_quantiles
            print(f"  winner energy [nJ]: p5={q[0.05]:.4g} "
                  f"median={q[0.5]:.4g} p95={q[0.95]:.4g} "
                  f"cvar(0.9)={var.cvar(0.9):.4g}")
            for impl, share in sorted(var.winner_share.items(),
                                      key=lambda kv: -kv[1]):
                print(f"    {impl:32s} wins {share:.0%} of variants")


if __name__ == "__main__":
    main()
