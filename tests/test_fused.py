"""Fused device-resident pipeline tests: on-device FilterEnergy parity,
sharding bit-identity, lazy grids, and the per-op (V, T, 3) plumbing.

Contracts under test:

  * `evaluate_select_suite` (evaluate + the three-tier masked argmin
    fused into one jitted pass), on a suite or a one-circuit suite,
    returns winners identical to the host-side parity reference
    (`select_best_batch` over the same call's materialized tensors) on
    every (circuit, variant) cell — including
    grids salted with NaN/±inf energies via pathological model variants,
    exact-tie grids (duplicate topology columns; lowest flat index wins),
    all-infeasible cells, and under latency/feasibility constraints;
  * an all-non-finite cell raises, exactly like `select_best_batch`;
  * the 1-device sharded path (`shard=True`) is bit-identical to the
    unsharded path — winners, per-winner metrics, and the full tensors;
  * lazy grids and their views materialize to the same arrays as a
    numpy copy of the root grid, and the fused payload is orders of
    magnitude below the full-tensor transfer;
  * one jit trace per fused sweep; float-only model changes do not
    retrace;
  * correlated generators may emit per-op ``(V, T, 3)`` fields which
    flow through the same kernels and match the scalar path cell by
    cell, with `_check_topo_axis` rejecting mismatched topology lists;
  * `explore_suite` equals the host reference end to end: its
    `VariationResult` (winners, shares, yields, quantiles/CVaR) is
    rebuilt from its grid's tensors through `select_best_batch`.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import batch, circuits as C
from repro.core.aig import AigStats
from repro.core.batch import (
    SuiteTable,
    TopologyTable,
    WorkloadTable,
    evaluate_select_suite,
    select_best_batch,
    table2_batch,
    trace_counts,
    winner_summary,
)
from repro.core.explorer import (
    ENERGY_QUANTILES,
    VariationResult,
    characterize_recipes,
    explore_suite,
)
from repro.core.mapping import schedule_stats
from repro.core.sram import (
    TOPOLOGY_LIBRARY,
    EnergyModel,
    ModelTable,
    SramTopology,
    evaluate,
)

METRIC_KEYS = (
    "latency_ns", "energy_nj", "power_mw", "throughput_gops", "tops_per_watt"
)


def stats_from_levels(levels):
    ops = [dict(nand=a, nor=b, inv=c) for a, b, c in levels]
    return AigStats(
        n_pis=8, n_pos=4, n_ands=0, n_levels=len(ops), ops_per_level=ops,
        nand_count=sum(l[0] for l in levels),
        nor_count=sum(l[1] for l in levels),
        inv_count=sum(l[2] for l in levels),
    )


def random_workload(rng, n_recipes=6, max_levels=9, max_ops=2000):
    items = []
    for i in range(n_recipes):
        n = int(rng.integers(1, max_levels + 1))
        levels = [
            tuple(int(x) for x in rng.integers(0, max_ops, size=3))
            for _ in range(n)
        ]
        items.append(((str(i),), stats_from_levels(levels)))
    return WorkloadTable.from_stats(items)


def salted_table(topos, n=6, seed=0, nan_frac=0.15):
    """A Monte-Carlo `ModelTable` whose ``p_ctrl_mw`` carries a (V, T)
    axis salted with NaN/+inf entries — physical-mode energies become
    non-finite exactly in those (variant, topology) columns, giving the
    fused filter real NaN-salted grids without tripping the
    all-non-finite error (row 0 stays clean)."""
    table = ModelTable.monte_carlo(n=n, sigma=0.2, seed=seed)
    rng = np.random.default_rng(seed + 1)
    p = np.broadcast_to(
        table.p_ctrl_mw[:, None], (n, len(topos))
    ).copy()
    salt = rng.random((n, len(topos)))
    salt[0] = 1.0  # nominal variant stays finite everywhere
    p[salt < nan_frac / 2] = np.nan
    p[(salt >= nan_frac / 2) & (salt < nan_frac)] = np.inf
    return dataclasses.replace(
        table, p_ctrl_mw=p,
        topology_names=tuple(t.name for t in topos.topologies),
    )


def host_reference(grid, max_latency_ns=None):
    """The host-side parity reference: (C, V) winners from the grid's
    materialized tensors via `SuiteVariationGrid.best_indices`
    (select_best_batch underneath)."""
    return grid.best_indices(max_latency_ns)


def one_circuit(work, topos, model, **kw):
    """``work`` swept as a one-circuit suite: its grid (a `VariationGrid`
    for a `ModelTable`, an `ExplorationGrid` for one model) and the
    ``(1, V)`` selection."""
    sg, sel = evaluate_select_suite(
        SuiteTable.from_workloads({"c": work}), topos, model, **kw
    )
    return (sg.variation(0) if isinstance(model, ModelTable) else sg.grid(0)), sel


@pytest.fixture(scope="module")
def workloads():
    rng = np.random.default_rng(42)
    work = random_workload(rng)
    suite = SuiteTable.from_workloads(
        {"a": work, "b": random_workload(rng, n_recipes=6)}
    )
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    return work, suite, topos


# ---------------------------------------------------------------------------
# Fused-vs-host winner parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["physical", "paper"])
@pytest.mark.parametrize("max_lat", [None, 40.0])
def test_fused_suite_matches_host_selection(workloads, mode, max_lat):
    _, suite, topos = workloads
    table = ModelTable.monte_carlo(n=5, sigma=0.3, seed=7)
    grid, sel = evaluate_select_suite(
        suite, topos, table, mode=mode, max_latency_ns=max_lat
    )
    host = host_reference(grid, max_lat)
    np.testing.assert_array_equal(sel.winner_idx.astype(np.int64), host)
    assert sel.winner_idx.dtype == np.int32
    # per-winner metrics equal the host gather on every metric
    c, v = host.shape
    for k in METRIC_KEYS:
        flat = getattr(grid, k).reshape(c, v, -1)
        ref = np.take_along_axis(flat, host[..., None], -1)[..., 0]
        np.testing.assert_array_equal(sel.winner_metrics[k], ref)
    # each circuit's tensors equal the circuit run as its own suite
    for name in suite.circuits:
        alone, _ = one_circuit(suite.workload(name), topos, table, mode=mode)
        for k in METRIC_KEYS + ("cycles", "fits"):
            np.testing.assert_array_equal(
                getattr(grid.variation(name), k), getattr(alone, k)
            )


def test_fused_matches_host_on_nan_salted_grids(workloads):
    _, suite, topos = workloads
    table = salted_table(topos, n=6, seed=3)
    grid, sel = evaluate_select_suite(suite, topos, table)
    assert not np.isfinite(grid.energy_nj).all()  # the salt is real
    assert np.isfinite(grid.energy_nj).any(axis=(2, 3)).all()
    np.testing.assert_array_equal(
        sel.winner_idx.astype(np.int64), host_reference(grid)
    )
    # NaN cells never win
    c, v = sel.winner_idx.shape
    assert np.isfinite(sel.winner_energy_nj).all()


def test_fused_all_non_finite_raises(workloads):
    work, suite, topos = workloads
    # every variant's clock is NaN -> every energy non-finite
    table = ModelTable.monte_carlo(n=3, sigma=0.1, seed=0)
    table = dataclasses.replace(
        table, f_clk_hz=np.full(3, np.nan)
    )
    with pytest.raises(ValueError, match="finite"):
        evaluate_select_suite(suite, topos, table)
    with pytest.raises(ValueError, match="finite"):
        one_circuit(work, topos, table)


def test_fused_ties_break_to_lowest_flat_index(workloads):
    """Duplicate topology columns produce exact-tie energies; the fused
    argmin must pick the lower flat index, like the host filter."""
    work, _, _ = workloads
    dup = TopologyTable.from_topologies(
        (TOPOLOGY_LIBRARY[4], TOPOLOGY_LIBRARY[4], TOPOLOGY_LIBRARY[4])
    )
    grid, sel = one_circuit(work, dup, ModelTable.monte_carlo(n=3, seed=1))
    host = grid.best_indices()
    np.testing.assert_array_equal(sel.winner_idx[0].astype(np.int64), host)
    # the duplicate columns really did tie, and column 0 won
    n_r = len(grid.recipes)
    assert (host < n_r).all()


def test_fused_all_infeasible_falls_through(workloads):
    """Nothing fits (huge workload) + nothing feasible: the fused filter
    falls through to the finite-energy tier exactly like the host."""
    rng = np.random.default_rng(9)
    big = random_workload(rng, n_recipes=4, max_ops=10_000_000)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:4])
    feas = np.zeros((1, 4), dtype=bool)
    table = ModelTable.monte_carlo(n=4, sigma=0.2, seed=2)
    grid, sel = one_circuit(big, topos, table, feasible=feas)
    assert not grid.fits.any()
    np.testing.assert_array_equal(
        sel.winner_idx[0].astype(np.int64), grid.best_indices()
    )


def test_fused_single_model_matches_host(workloads):
    work, suite, topos = workloads
    em = EnergyModel()
    sg, sel = evaluate_select_suite(suite, topos, em)
    assert sel.winner_idx.shape == (len(suite), 1)
    for i, name in enumerate(suite.circuits):
        assert int(sel.winner_idx[i, 0]) == sg.grid(name).best_index()
    g, s = one_circuit(work, topos, em)
    assert s.winner_idx.shape == (1, 1)
    assert int(s.winner_idx[0, 0]) == g.best_index()
    assert g.model == em


# ---------------------------------------------------------------------------
# Sharding, laziness, payload, trace counts
# ---------------------------------------------------------------------------


def test_one_device_sharded_is_bit_identical(workloads):
    """`shard=True` on a single device builds a 1-device mesh; every
    output — winners, per-winner metrics, the full tensors — must be
    bit-identical to the unsharded path."""
    _, suite, topos = workloads
    table = ModelTable.monte_carlo(n=4, sigma=0.25, seed=11)
    g_plain, s_plain = evaluate_select_suite(
        suite, topos, table, shard=False
    )
    g_shard, s_shard = evaluate_select_suite(suite, topos, table, shard=True)
    assert not s_plain.sharded and s_shard.sharded
    np.testing.assert_array_equal(s_shard.winner_idx, s_plain.winner_idx)
    np.testing.assert_array_equal(
        s_shard.nominal_latency_ns, s_plain.nominal_latency_ns
    )
    for k in METRIC_KEYS:
        np.testing.assert_array_equal(
            s_shard.winner_metrics[k], s_plain.winner_metrics[k]
        )
        np.testing.assert_array_equal(
            np.asarray(getattr(g_shard, k)), np.asarray(getattr(g_plain, k))
        )


LAZY_KEYS = METRIC_KEYS + ("cycles", "active_macro_cycles", "fits")

# view -> (swept over a ModelTable, make it from the suite grid, the
# arguments of its ``cell``, the deferred views it makes)
LAZY_VIEWS = {
    "variation": (True, lambda g: g.variation("b"), (1, 2, 3), 1),
    "variation.grid": (True, lambda g: g.variation("b").grid(2), (2, 3), 2),
    "suite": (True, lambda g: g.suite(1), ("b", 2, 3), 1),
    "suite.grid": (True, lambda g: g.suite(1).grid("b"), (2, 3), 2),
    "nominal.grid": (False, lambda g: g.grid("b"), (2, 3), 1),
}


def _eager(grid):
    """The eager reference: a copy of a lazy root grid whose fields are
    numpy arrays (``np.asarray`` of each device field)."""
    return dataclasses.replace(
        grid, **{k: np.asarray(grid._raw(k)) for k in LAZY_KEYS}
    )


def _check_lazy_view(suite, topos, model, make, at, n_views):
    lazy_root, _ = evaluate_select_suite(suite, topos, model)
    eager_root = _eager(evaluate_select_suite(suite, topos, model)[0])
    made = dict(batch.VIEW_COUNTS)
    lazy, eager = make(lazy_root), make(eager_root)
    # making a view (of a view) dispatches no slice; numpy-backed grids
    # make plain numpy views, which are not counted
    assert batch.VIEW_COUNTS == dict(
        views=made["views"] + n_views, slices=made["slices"])
    assert lazy._stored("energy_nj").shape == eager.energy_nj.shape
    # a cell gathers single elements of the root at the composed index
    assert lazy.cell(*at) == eager.cell(*at)
    assert batch.VIEW_COUNTS["slices"] == made["slices"]
    # _raw dispatches the one slice: a device array with its placement
    raw = lazy._raw("energy_nj")
    assert not isinstance(raw, np.ndarray) and raw.sharding.device_set
    assert batch.VIEW_COUNTS["slices"] == made["slices"] + 1
    assert lazy._raw("energy_nj") is raw  # resolved once, cached
    for k in LAZY_KEYS:
        got, want = getattr(lazy, k), getattr(eager, k)
        assert isinstance(got, np.ndarray), k
        assert (got.dtype, got.shape) == (want.dtype, want.shape), k
        assert got.tobytes() == want.tobytes(), k


@pytest.mark.parametrize("view", [None, *LAZY_VIEWS])
def test_lazy_grid_materializes_identically(workloads, view):
    _, suite, topos = workloads
    table = ModelTable.monte_carlo(n=3, sigma=0.2, seed=5)
    if view is not None:
        swept, make, at, n_views = LAZY_VIEWS[view]
        model = table if swept else EnergyModel()
        _check_lazy_view(suite, topos, model, make, at, n_views)
        return
    lazy_grid, _ = evaluate_select_suite(suite, topos, table)
    eager_grid = _eager(evaluate_select_suite(suite, topos, table)[0])
    # before access the lazy fields are device arrays, not numpy
    assert not isinstance(lazy_grid._raw("energy_nj"), np.ndarray)
    for k in LAZY_KEYS:
        np.testing.assert_array_equal(
            getattr(lazy_grid, k), getattr(eager_grid, k)
        )
    # access materialized + cached the field in place
    assert isinstance(lazy_grid._raw("energy_nj"), np.ndarray)
    # sliced views and shape queries inherit laziness
    lazy2, _ = evaluate_select_suite(suite, topos, table)
    vgrid = lazy2.variation(suite.circuits[0])
    assert lazy2.size == eager_grid.size  # .size must not materialize
    assert not isinstance(lazy2._raw("energy_nj"), np.ndarray)
    np.testing.assert_array_equal(
        vgrid.energy_nj, eager_grid.variation(suite.circuits[0]).energy_nj
    )


def test_fused_payload_is_small(workloads):
    _, suite, topos = workloads
    table = ModelTable.monte_carlo(n=8, sigma=0.2, seed=6)
    grid, sel = evaluate_select_suite(suite, topos, table)
    full = sum(getattr(grid, k).nbytes for k in METRIC_KEYS)
    assert sel.payload_bytes < full / 10
    c, v = len(suite), len(table)
    assert sel.winner_idx.nbytes == c * v * 4  # (C, V) int32


def test_fused_traces_once_and_float_changes_do_not_retrace():
    rng = np.random.default_rng(77)
    work = random_workload(rng, n_recipes=7)  # unique shape
    suite = SuiteTable.from_workloads({"x": work, "y": work, "z": work})
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    before = trace_counts().get("fused_suite", 0)
    _, s1 = evaluate_select_suite(
        suite, topos, ModelTable.monte_carlo(n=5, sigma=0.1, seed=0)
    )
    assert trace_counts().get("fused_suite", 0) == before + 1
    # float-only model change: served from the jit cache
    _, s2 = evaluate_select_suite(
        suite, topos, ModelTable.monte_carlo(n=5, sigma=0.4, seed=9)
    )
    assert trace_counts().get("fused_suite", 0) == before + 1
    assert not np.array_equal(s1.winner_energy_nj, s2.winner_energy_nj)
    # changing the latency *bound* does not retrace (traced operand)...
    _, _ = evaluate_select_suite(
        suite, topos, ModelTable.monte_carlo(n=5, seed=1),
        max_latency_ns=100.0,
    )
    after_lat = trace_counts().get("fused_suite", 0)
    _, _ = evaluate_select_suite(
        suite, topos, ModelTable.monte_carlo(n=5, seed=2),
        max_latency_ns=55.0,
    )
    assert trace_counts().get("fused_suite", 0) == after_lat


# ---------------------------------------------------------------------------
# Per-op (V, T, 3) correlated fields
# ---------------------------------------------------------------------------


def test_per_op_topology_axis_shapes_and_validation():
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=4, sigma=0.3, seed=0,
        fields=("e_op_fj", "e_op_marginal_fj", "bitcell_um2"),
    )
    assert table.e_op_fj.shape == (4, 12, 3)
    assert table.e_op_marginal_fj.shape == (4, 12, 3)
    assert table.bitcell_um2.shape == (4, 12)
    assert table.n_topologies == 12
    assert table.model(0) == EnergyModel()  # row 0 nominal
    assert table.uniform_row(0) and not table.uniform_row(1)
    # topology= materializes one column; without it the row raises
    m = table.model(1, topology=3)
    assert m.e_op_marginal_fj == tuple(
        float(x) for x in table.e_op_marginal_fj[1, 3]
    )
    with pytest.raises(ValueError, match="topology-"):
        table.model(1)
    # _check_topo_axis: a mismatched per-op axis is rejected
    short = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:5])
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="per-topology axis|generated for"):
        one_circuit(random_workload(rng), short, table)
    with pytest.raises(ValueError, match="per-topology axis|generated for"):
        table2_batch(short, table)
    # mixed per-op/scalar widths inside one table are rejected
    kw = {
        f.name: getattr(table, f.name)
        for f in dataclasses.fields(EnergyModel)
    }
    kw["e_op_fj"] = np.ones((4, 5, 3))
    with pytest.raises(ValueError, match="per-topology width"):
        ModelTable(names=table.names, **kw)
    # malformed trailing axis is rejected
    kw["e_op_fj"] = np.ones((4, 12, 2))
    with pytest.raises(ValueError, match="per-op"):
        ModelTable(names=table.names, **kw)


def test_per_op_correlated_sweep_matches_scalar_path():
    """Every (variant, topology) cell of a per-op (V, T, 3) sweep equals
    the scalar path run with that cell's materialized EnergyModel."""
    rng = np.random.default_rng(15)
    items = [
        ((str(i),), stats_from_levels(
            [tuple(int(x) for x in rng.integers(0, 800, 3))
             for _ in range(int(rng.integers(1, 6)))]
        ))
        for i in range(3)
    ]
    work = WorkloadTable.from_stats(items)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=3, sigma=0.4, seed=8,
        fields=("e_op_fj", "e_op_marginal_fj"),
    )
    vg, sel = one_circuit(work, topos, table)
    for v in range(3):
        for t in range(len(TOPOLOGY_LIBRARY)):
            m = table.model(v, topology=t)
            topo = TOPOLOGY_LIBRARY[t]
            for r, (_, stats) in enumerate(items):
                met = evaluate(schedule_stats(stats, topo), topo, m)
                np.testing.assert_allclose(
                    vg.energy_nj[v, t, r], met.energy_nj, rtol=1e-12
                )
    # table2 over the per-op table matches column materialization
    tb = table2_batch(topos, table)
    for v in range(3):
        for t in range(len(TOPOLOGY_LIBRARY)):
            ref = table2_batch(
                TopologyTable.from_topologies([TOPOLOGY_LIBRARY[t]]),
                table.model(v, topology=t),
            )
            np.testing.assert_allclose(
                tb["power_mw"][v, t], ref["power_mw"][0], rtol=1e-12
            )
    # ...and the fused filter handles the (V, T, 3) shape too
    np.testing.assert_array_equal(
        sel.winner_idx[0].astype(np.int64), vg.best_indices()
    )


# ---------------------------------------------------------------------------
# explore_suite end to end: fused == host reference, quantiles/CVaR
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bar_suite():
    suite = C.benchmark_suite(scale="tiny", only=("bar",))
    cha = {"bar": characterize_recipes(suite["bar"])}
    return suite, cha


def host_variation(vgrid, max_latency_ns=None):
    """The host reference of a `VariationResult`: winners, shares,
    yields and winner energies recomputed from the grid's materialized
    tensors through `select_best_batch`."""
    v = len(vgrid.models)
    energy = vgrid.energy_nj.reshape(v, -1)
    feas = np.broadcast_to(vgrid.feasible[:, None], vgrid.fits.shape)
    idx = select_best_batch(
        energy, vgrid.fits.reshape(1, -1),
        latency=vgrid.latency_ns.reshape(v, -1), max_latency=max_latency_ns,
        feasible=feas.reshape(1, -1),
    )
    pairs = [vgrid.unravel(int(i)) for i in idx]
    winners = [(vgrid.recipes[ri], vgrid.topologies[ti]) for ti, ri in pairs]
    share, best_yield = winner_summary(
        [f"{topo.name}/{','.join(recipe) or '-'}" for recipe, topo in winners]
    )
    ti0, ri0 = pairs[0]
    ok = np.full(v, bool(vgrid.fits[ti0, ri0]))
    if max_latency_ns is not None:
        ok &= vgrid.latency_ns[:, ti0, ri0] <= max_latency_ns
    winner_energy = energy[np.arange(v), idx]
    return VariationResult(
        models=vgrid.models, grid=vgrid, winners=winners,
        winner_share=share, best_yield=best_yield,
        latency_yield=float(np.mean(ok)), winner_energy_nj=winner_energy,
        energy_quantiles={
            q: float(np.quantile(winner_energy, q)) for q in ENERGY_QUANTILES
        },
    )


def test_explore_suite_fused_equals_host_path(bar_suite):
    suite, cha = bar_suite
    table = ModelTable.monte_carlo(n=6, sigma=0.25, seed=4)
    fused = explore_suite(suite, cha=cha, model_sweep=table)
    for name in suite:
        f = fused[name]
        vf, vh = f.variation, host_variation(f.variation.grid)
        recipe, topo = vh.winners[0]
        assert (f.best.recipe, f.best.topo) == (recipe, topo)
        ref = evaluate(
            schedule_stats(cha[name][recipe], topo), topo, table.model(0)
        )
        assert f.best.metrics.energy_nj == ref.energy_nj
        assert vf.winners == vh.winners
        assert vf.winner_share == vh.winner_share
        assert vf.best_yield == vh.best_yield
        assert vf.latency_yield == vh.latency_yield
        np.testing.assert_array_equal(
            vf.winner_energy_nj, vh.winner_energy_nj
        )
        assert vf.energy_quantiles == vh.energy_quantiles
        assert vf.cvar(0.9) == vh.cvar(0.9)


def test_explore_suite_fused_with_latency_bound(bar_suite):
    suite, cha = bar_suite
    table = ModelTable.corners(spread=0.2)
    fused = explore_suite(
        suite, cha=cha, model_sweep=table, max_latency_ns=30.0
    )
    for name in suite:
        vf = fused[name].variation
        vh = host_variation(vf.grid, max_latency_ns=30.0)
        assert vf.winners == vh.winners
        assert vf.latency_yield == vh.latency_yield


def test_fused_matches_host_on_full_ci_grid_with_nan_salt(bar_suite):
    """Acceptance: fused winners == host `select_best_batch` winners on
    every (circuit, variant) cell of the full 65 x 12 CI grid, with
    NaN/+inf-salted model variants in the sweep."""
    suite, cha = bar_suite
    suite_table = SuiteTable.from_cha(cha)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = salted_table(topos, n=8, seed=17)
    for max_lat in (None, 40.0):
        grid, sel = evaluate_select_suite(
            suite_table, topos, table, max_latency_ns=max_lat
        )
        assert grid.energy_nj.shape[2:] == (12, 65)
        assert not np.isfinite(grid.energy_nj).all()
        np.testing.assert_array_equal(
            sel.winner_idx.astype(np.int64), grid.best_indices(max_lat)
        )


def test_variation_quantiles_and_cvar_reference(bar_suite):
    suite, cha = bar_suite
    table = ModelTable.monte_carlo(n=16, sigma=0.3, seed=12)
    var = explore_suite(suite, cha=cha, model_sweep=table)["bar"].variation
    e = var.winner_energy_nj
    assert e.shape == (16,)
    # quantiles are plain np.quantile over the winner energies
    for q, val in var.energy_quantiles.items():
        assert val == pytest.approx(float(np.quantile(e, q)))
    # cvar: mean of the worst (1 - alpha) tail, monotone in alpha
    srt = np.sort(e)
    assert var.cvar(0.75) == pytest.approx(srt[-4:].mean())
    assert var.cvar(0.0) == pytest.approx(e.mean())
    assert var.cvar(0.9) <= var.cvar(0.95) + 1e-18
    assert var.cvar(0.95) == pytest.approx(srt[-1])
    with pytest.raises(ValueError, match="alpha"):
        var.cvar(1.0)
    # winner energies equal the per-variant winner cells of the grid
    flat = var.grid.energy_nj.reshape(16, -1)
    idx = var.grid.best_indices()
    np.testing.assert_array_equal(e, flat[np.arange(16), idx])
