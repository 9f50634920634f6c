"""Parity + semantics tests for the tensorized exploration engine
(core/batch.py) against the scalar reference path.

The contract under test: ``backend="jax"`` is the same Algorithm I as
``backend="python"`` — same schedules (exact integers), same energies
(float round-off), same argmin picks (including tie-breaking) — just
batched into one jitted grid.
"""

import json
import pickle

import numpy as np
import pytest

from repro.core import circuits as C
from repro.core.aig import AigStats
from repro.core.batch import (
    SuiteTable,
    TopologyTable,
    WorkloadTable,
    evaluate_select_suite,
    select_best,
    select_best_worst,
    table2_batch,
)
from repro.core.explorer import (
    best_worst,
    characterize_recipes,
    explore,
    explore_suite,
)
from repro.core.mapping import schedule_stats
from repro.core.sram import (
    TOPOLOGY_LIBRARY,
    EnergyModel,
    SramTopology,
    evaluate,
    table2_metrics,
)

EM = EnergyModel()


def stats_from_levels(levels):
    ops = [dict(nand=a, nor=b, inv=c) for a, b, c in levels]
    return AigStats(
        n_pis=8, n_pos=4, n_ands=0, n_levels=len(ops), ops_per_level=ops,
        nand_count=sum(l[0] for l in levels),
        nor_count=sum(l[1] for l in levels),
        inv_count=sum(l[2] for l in levels),
    )


# Synthetic workloads hitting the structural edge cases: empty levels,
# single-type levels, wide levels, deep-narrow shapes, capacity misfits.
SYNTH = [
    ((), stats_from_levels([(3, 1, 0), (0, 0, 1)])),
    (("a",), stats_from_levels([(0, 0, 0), (5, 0, 0), (0, 7, 2)])),
    (("b",), stats_from_levels([(400, 130, 65)] * 7)),
    (("c",), stats_from_levels([(1, 0, 0)] * 40)),
    (("d",), stats_from_levels([(9000, 9000, 500)])),  # doesn't fit 4KB
]


def one_circuit_grid(items, topos, model=EM, **kw):
    """``items`` ((recipe, stats) pairs) swept as a one-circuit suite by
    the fused program: the circuit's `ExplorationGrid`."""
    suite = SuiteTable.from_cha({"c": dict(items)})
    sg, _ = evaluate_select_suite(suite, topos, model, **kw)
    return sg.grid(0)


# ---------------------------------------------------------------------------
# Grid vs scalar parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("discipline", ["list", "levels"])
def test_schedule_batch_matches_scalar(discipline):
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    grid = one_circuit_grid(SYNTH, topos, discipline=discipline)
    for ti, topo in enumerate(TOPOLOGY_LIBRARY):
        for ri, (_, st) in enumerate(SYNTH):
            ref = schedule_stats(st, topo, discipline=discipline)
            assert grid.cycles[ti, ri] == ref.total_cycles
            assert grid.active_macro_cycles[ti, ri] == ref.active_macro_cycles
            assert bool(grid.fits[ti, ri]) == ref.fits


@pytest.mark.parametrize("mode", ["physical", "paper"])
@pytest.mark.parametrize("discipline", ["list", "levels"])
def test_evaluate_batch_matches_scalar(mode, discipline):
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    grid = one_circuit_grid(SYNTH, topos, mode=mode, discipline=discipline)
    for ti, topo in enumerate(TOPOLOGY_LIBRARY):
        for ri, (_, st) in enumerate(SYNTH):
            sched = schedule_stats(st, topo, discipline=discipline)
            ref = evaluate(sched, topo, EM, mode=mode)
            assert grid.cycles[ti, ri] == ref.cycles
            assert bool(grid.fits[ti, ri]) == sched.fits
            np.testing.assert_allclose(
                grid.energy_nj[ti, ri], ref.energy_nj, rtol=1e-12
            )
            np.testing.assert_allclose(
                grid.latency_ns[ti, ri], ref.latency_ns, rtol=1e-12
            )
            np.testing.assert_allclose(
                grid.power_mw[ti, ri], ref.power_mw, rtol=1e-12
            )
            np.testing.assert_allclose(
                grid.throughput_gops[ti, ri], ref.throughput_gops, rtol=1e-12
            )
            np.testing.assert_allclose(
                grid.tops_per_watt[ti, ri], ref.tops_per_watt, rtol=1e-12
            )


# ---------------------------------------------------------------------------
# Full-recipe backend parity (the ISSUE acceptance grid: 65 recipes x 12
# topologies per circuit, both accounting modes)
# ---------------------------------------------------------------------------

PARITY_CIRCUITS = {
    "bar-16": lambda: C.gen_barrel_shifter(16),
    "sqrt-8": lambda: C.gen_sqrt(8),
    "adder-32": lambda: C.gen_adder(32),
}


@pytest.fixture(scope="module", params=sorted(PARITY_CIRCUITS))
def full_cha(request):
    rtl = PARITY_CIRCUITS[request.param]()
    return rtl, characterize_recipes(rtl)  # all 64 recipes + baseline


@pytest.mark.parametrize("mode", ["physical", "paper"])
def test_backend_parity_full_grid(full_cha, mode):
    rtl, cha = full_cha
    py = explore(rtl, cha=cha, mode=mode, backend="python")
    jx = explore(rtl, cha=cha, mode=mode, backend="jax")

    assert py.n_recipes == jx.n_recipes == 65
    assert py.n_evaluations == jx.n_evaluations == 65 * 12

    # identical argmin pick, identical energy (best is re-materialized
    # through the scalar model, so this is exact, well inside 1e-6 nJ)
    assert jx.best.recipe == py.best.recipe
    assert jx.best.topo == py.best.topo
    assert abs(jx.best.metrics.energy_nj - py.best.metrics.energy_nj) < 1e-6
    assert jx.best.metrics.cycles == py.best.metrics.cycles

    # full-grid value parity
    g = jx.grid
    assert g is not None and g.mode == mode
    for e in py.evaluations:
        ti = g.topologies.index(e.topo)
        ri = g.recipes.index(e.recipe)
        assert g.cycles[ti, ri] == e.schedule.total_cycles
        assert g.active_macro_cycles[ti, ri] == e.schedule.active_macro_cycles
        assert bool(g.fits[ti, ri]) == e.schedule.fits
        np.testing.assert_allclose(
            g.energy_nj[ti, ri], e.metrics.energy_nj, rtol=1e-12
        )

    # best/worst companion agrees too
    b_py, w_py = best_worst(py)
    b_jx, w_jx = best_worst(jx)
    assert (b_jx.recipe, b_jx.topo) == (b_py.recipe, b_py.topo)
    assert (w_jx.recipe, w_jx.topo) == (w_py.recipe, w_py.topo)
    assert abs(w_jx.metrics.energy_nj - w_py.metrics.energy_nj) < 1e-6


def test_explore_honors_recipes_restriction_with_cha(full_cha):
    rtl, cha = full_cha
    for backend in ("python", "jax"):
        res = explore(rtl, cha=cha, recipes=[("Ba",), ("Rw",)],
                      backend=backend)
        assert res.n_recipes == 3  # () + Ba + Rw, not all 65 cached
        assert res.n_evaluations == 3 * 12
    with pytest.raises(ValueError, match="missing requested"):
        explore(rtl, cha={(): cha[()]}, recipes=[("Ba",)])


def test_backend_parity_latency_constraint_and_pseudocode_sweep(full_cha):
    rtl, cha = full_cha
    free = explore(rtl, cha=cha, backend="jax")
    cap = free.best.metrics.latency_ns * 0.9
    for kw in (
        dict(max_latency_ns=cap),
        dict(full_sweep=False),
        dict(discipline="levels"),
    ):
        py = explore(rtl, cha=cha, backend="python", **kw)
        jx = explore(rtl, cha=cha, backend="jax", **kw)
        assert (jx.best.recipe, jx.best.topo) == (py.best.recipe, py.best.topo)
        assert abs(jx.best.metrics.energy_nj - py.best.metrics.energy_nj) < 1e-6
        assert jx.n_evaluations == py.n_evaluations


GRID_FIELDS = (
    "cycles", "active_macro_cycles", "fits", "latency_ns", "energy_nj",
    "power_mw", "throughput_gops", "tops_per_watt", "area_mm2", "feasible",
)


@pytest.mark.parametrize("bounded", [False, True], ids=["free", "latency"])
def test_explore_jax_is_a_one_circuit_explore_suite(full_cha, bounded):
    """`explore(backend="jax")` runs the circuit as a one-circuit suite:
    the same winner and the same grid as `explore_suite` on that one
    circuit, with a latency bound and without one."""
    rtl, cha = full_cha
    kw = {}
    if bounded:
        free = explore(rtl, cha=cha, backend="jax")
        kw["max_latency_ns"] = free.best.metrics.latency_ns * 0.9
    one = explore(rtl, cha=cha, backend="jax", **kw)
    suite = explore_suite({rtl.name: rtl}, cha={rtl.name: cha}, **kw)[rtl.name]
    assert one.backend == suite.backend == "jax"
    assert (one.best.recipe, one.best.topo) == (suite.best.recipe, suite.best.topo)
    assert one.best.metrics.energy_nj == suite.best.metrics.energy_nj
    a, b = one.grid, suite.grid
    assert (a.recipes, a.topologies) == (b.recipes, b.topologies)
    assert (a.mode, a.discipline, a.model) == (b.mode, b.discipline, b.model)
    for field in GRID_FIELDS:
        x, y = getattr(a, field), getattr(b, field)
        assert isinstance(x, np.ndarray), field
        assert x.dtype == y.dtype and np.array_equal(x, y), field


# ---------------------------------------------------------------------------
# select_best / select_best_worst semantics (the shared FilterEnergy)
# ---------------------------------------------------------------------------


def test_select_best_admissibility_tiers():
    energy = np.array([5.0, 1.0, 3.0, 2.0])
    fits = np.array([True, True, True, False])
    # plain: global fitting argmin
    assert select_best(energy, fits) == 1
    # feasible knocks out the minimum
    feasible = np.array([True, False, True, True])
    assert select_best(energy, fits, feasible=feasible) == 2
    # latency constraint knocks out the feasible minimum too
    lat = np.array([1.0, 1.0, 9.0, 1.0])
    assert select_best(energy, fits, latency=lat, max_latency=5.0,
                       feasible=feasible) == 0
    # tier 2: constraint empties the pool -> fall back to fits-only argmin
    assert select_best(energy, fits, latency=lat, max_latency=0.5) == 1
    # tier 3: nothing fits -> global argmin
    assert select_best(energy, np.zeros(4, dtype=bool)) == 1


def test_select_best_tie_breaks_to_first():
    energy = np.array([2.0, 1.0, 1.0, 1.0])
    fits = np.array([True, False, True, True])
    assert select_best(energy, fits) == 2  # first *fitting* minimum
    b, w = select_best_worst(energy, fits)
    assert b == 2 and w == 0


def test_select_best_matches_mesh_explorer_fallback_chain():
    """The chain mesh_explorer used before the port: fits -> (latency or
    fits) -> everything."""
    energy = np.array([4.0, 2.0, 3.0])
    fits = np.array([False, True, True])
    lat = np.array([1.0, 9.0, 9.0])
    # latency filter empties the fitting pool -> fitting argmin survives
    assert select_best(energy, fits, latency=lat, max_latency=2.0) == 1
    with pytest.raises(ValueError):
        select_best(np.array([]), np.array([], dtype=bool))


def test_grid_flat_order_is_topology_major():
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:4])
    grid = one_circuit_grid(SYNTH[:3], topos)
    i = grid.best_index()
    ti, ri = grid.unravel(i)
    assert grid.energy_nj.ravel()[i] == grid.energy_nj[ti, ri]
    # same winner as a scalar argmin in the python loop order
    flat = [
        (grid.energy_nj[t, r], bool(grid.fits[t, r]))
        for t in range(len(topos.topologies))
        for r in range(len(grid.recipes))
    ]
    pool = [e for e, f in flat if f] or [e for e, _ in flat]
    assert grid.energy_nj.ravel()[i] == min(pool)


# ---------------------------------------------------------------------------
# Tables
# ---------------------------------------------------------------------------


def test_workload_table_padding_and_totals():
    work = WorkloadTable.from_stats(SYNTH, pad_levels_to=64)
    assert work.ops.shape == (5, 64, 3)
    assert work.n_levels.tolist() == [2, 3, 7, 40, 1]
    assert work.gates.tolist() == [
        s.total_gates for _, s in SYNTH
    ]
    # padding rows are zero
    assert work.ops[0, 2:].sum() == 0


# ---------------------------------------------------------------------------
# AigStats level-op matrix memo
# ---------------------------------------------------------------------------

LEVELS = [(3, 1, 0), (0, 0, 1), (400, 130, 65), (0, 7, 2)]


class _CountingLevels(list):
    """An ``ops_per_level`` list that counts its iterations."""

    reads = 0

    def __iter__(self):
        type(self).reads += 1
        return super().__iter__()


def test_ops_matrix_is_built_once_and_read_only(monkeypatch):
    rec = stats_from_levels(LEVELS)
    monkeypatch.setattr(_CountingLevels, "reads", 0)
    rec.ops_per_level = _CountingLevels(rec.ops_per_level)
    assert not rec.has_ops_matrix
    first = rec.ops_matrix()
    assert _CountingLevels.reads == 1 and rec.has_ops_matrix
    assert first.dtype == np.int64 and first.tolist() == [list(l) for l in LEVELS]
    again = rec.ops_matrix()
    assert again is first
    assert _CountingLevels.reads == 1
    assert not again.flags.writeable
    with pytest.raises(ValueError):
        again[0, 0] = 1
    # a level that leaves out an op type reads as zero of that type
    sparse = stats_from_levels([(2, 0, 0)])
    sparse.ops_per_level[0] = dict(nand=2)
    assert sparse.ops_matrix().tolist() == [[2, 0, 0]]
    assert stats_from_levels([]).ops_matrix().shape == (0, 3)


def test_ops_matrix_memo_leaves_record_form_unchanged():
    rec, twin = stats_from_levels(LEVELS), stats_from_levels(LEVELS)
    before = json.dumps(rec.to_dict(), sort_keys=True)
    rec.ops_matrix()
    assert json.dumps(rec.to_dict(), sort_keys=True) == before
    assert rec.to_dict() == twin.to_dict()
    assert rec == twin and repr(rec) == repr(twin)
    back = AigStats.from_dict(rec.to_dict())
    assert back == rec and not back.has_ops_matrix


def test_ops_matrix_memo_is_not_pickled():
    rec, twin = stats_from_levels(LEVELS), stats_from_levels(LEVELS)
    rec.ops_matrix()
    blob = pickle.dumps(rec)
    assert blob == pickle.dumps(twin)
    clone = pickle.loads(blob)
    assert clone == rec and not clone.has_ops_matrix
    assert np.array_equal(clone.ops_matrix(), rec.ops_matrix())


def test_characterization_builds_no_ops_matrix():
    stats = C.gen_adder(4).characterize()
    assert not stats.has_ops_matrix


def test_topology_table_matches_library():
    tt = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    for i, t in enumerate(TOPOLOGY_LIBRARY):
        assert tt.rows[i] == t.rows
        assert tt.cols[i] == t.cols
        assert tt.total_bits[i] == t.total_bits
        assert tt.ops_per_cycle[i] == t.ops_per_cycle_per_macro
        assert tt.is_single[i] == (t.n_macros == 1)
    with pytest.raises(ValueError):
        TopologyTable.from_topologies([])


def test_table2_batch_matches_scalar():
    topos = [SramTopology(8, 1), SramTopology(8, 3), SramTopology(16, 3)]
    tt = TopologyTable.from_topologies(topos)
    for frac in (0.0, 0.5, 1.0):
        batched = table2_batch(tt, EM, nor_fraction=frac)
        for i, topo in enumerate(topos):
            ref = table2_metrics(topo, EM, nor_fraction=frac)
            for k, v in ref.items():
                np.testing.assert_allclose(batched[k][i], v, rtol=1e-12)
