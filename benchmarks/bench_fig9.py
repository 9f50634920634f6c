"""Fig 9 — power / latency / energy across the 12 rCiM topologies.

Two sections (both dimensions of the paper's 6912-implementation study,
decoupled so the sweep stays CPU-tractable):

  A. *recipe sweep* — all 64 synthesis recipes x 12 topologies per circuit
     at ``scale`` (tiny/default).  Shows the recipe-quality spread the
     paper's Table I best/worst rows rely on.

  B. *topology trends* — paper-scale circuits (characterization only, no
     transforms) swept over the 12 topologies.  This is the width-bound
     regime where Fig 9's claims live: 3-macro vs 1-macro energy (-39%),
     macro-doubling energy drop (-47%), 6-macro latency (-66% vs single).

Both sections ride the suite-level engine (core/batch.py): section A is
one `explorer.explore_suite` call (suite characterization + a single
circuits x recipes x topologies sweep); section B stacks the paper-scale
baselines into a `SuiteTable` and runs ONE `evaluate_select_suite` call
for all circuits x 12 topologies.
"""

from __future__ import annotations

import time

from repro.core import circuits as C
from repro.core.batch import SuiteTable, TopologyTable, evaluate_select_suite
from repro.core.explorer import explore_suite
from repro.core.sram import (
    MACRO_SIZES_KB,
    TOPOLOGY_LIBRARY,
    EnergyModel,
    SramTopology,
    evaluate,
)
from repro.core.mapping import schedule_stats

from .common import Csv


def run(csv: Csv, scale: str = "tiny", recipes=None, backend: str = "jax",
        cache=None) -> dict:
    # ---- section A: recipe sweep (one suite-level call) --------------------
    suite = C.benchmark_suite(scale=scale)
    t0 = time.time()
    results = explore_suite(suite, recipes=recipes, backend=backend,
                            cache=cache)
    total = 0
    for name, res in results.items():
        total += res.n_evaluations
        es = res.sweep_energies(fits_only=True)
        spread = (float(es.max()) / float(es.min())) if es.size else 0.0
        csv.add(
            f"fig9/recipes/{name}", res.wall_s * 1e6,
            f"impls={res.n_evaluations};best={res.best.topo.name}"
            f"({','.join(res.best.recipe) or '-'});"
            f"energy_spread={spread:.1f}x",
        )
    csv.add("fig9/recipes/TOTAL", (time.time() - t0) * 1e6,
            f"implementations={total}(paper 6912 at server scale)")

    # ---- section B: topology trends at paper scale -------------------------
    em = EnergyModel()
    topo_table = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    topo_index = {
        (t.macro_kb, t.n_macros): i for i, t in enumerate(TOPOLOGY_LIBRARY)
    }
    paper_suite = C.benchmark_suite(scale="paper")
    stats = {name: rtl.characterize() for name, rtl in paper_suite.items()}

    if backend == "jax":
        # ONE jitted pass: all circuits x 12 topologies (baseline recipe).
        sg, _ = evaluate_select_suite(
            SuiteTable.from_cha({n: {(): s} for n, s in stats.items()}),
            topo_table, em,
        )

        def met(name, kb, m):
            g = sg.grid(name)
            i = topo_index[(kb, m)]
            return float(g.energy_nj[i, 0]), float(g.latency_ns[i, 0])
    else:
        def met(name, kb, m):
            t = SramTopology(kb, m)
            ref = evaluate(schedule_stats(stats[name], t), t, em)
            return ref.energy_nj, ref.latency_ns

    trends = dict(d3m=[], d48=[], lat6=[], best6=[])
    for name in paper_suite:
        st = stats[name]

        def e(kb, m):
            return met(name, kb, m)[0]

        def lat(kb, m):
            return met(name, kb, m)[1]

        d48 = 100 * (1 - e(8, 1) / e(4, 1))
        d3m = sum(
            100 * (1 - e(kb, 3) / e(kb, 1)) for kb in MACRO_SIZES_KB
        ) / len(MACRO_SIZES_KB)
        lat6 = sum(
            100 * (1 - lat(kb, 6) / lat(kb, 1)) for kb in MACRO_SIZES_KB
        ) / len(MACRO_SIZES_KB)
        best6 = 100 * (
            1 - min(e(kb, 6) for kb in MACRO_SIZES_KB) / e(4, 1)
        )
        for k, v in zip(("d3m", "d48", "lat6", "best6"), (d3m, d48, lat6, best6)):
            trends[k].append(v)
        csv.add(
            f"fig9/topology/{name}", 0.0,
            f"gates={st.total_gates};levels={st.n_levels};"
            f"E_3m_vs_1m={d3m:.0f}%;E_4to8KB={d48:.0f}%;"
            f"T_6m_vs_1m={lat6:.0f}%;E_best6_vs_1x4KB={best6:.0f}%",
        )
    n = len(trends["d3m"])
    csv.add(
        "fig9/topology/AVERAGE", 0.0,
        f"E_3m_vs_1m={sum(trends['d3m'])/n:.1f}%(paper 39);"
        f"E_4to8KB={sum(trends['d48'])/n:.1f}%(paper 47);"
        f"T_6m_vs_1m={sum(trends['lat6'])/n:.1f}%(paper 66);"
        f"E_best6_vs_1x4KB={sum(trends['best6'])/n:.1f}%(paper 80.9)",
    )
    return results
