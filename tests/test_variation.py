"""Parity + no-recompile tests for the dynamic energy-model axis.

The contract under test (the yield/variation engine of core/batch.py):

  * a 1-variant `ModelTable` sweep is **bit-identical** to the
    static-`EnergyModel` path, across grids, accounting modes, and
    scheduling disciplines (the model constants moved from jit statics
    to traced operands without changing a single float op);
  * an N-variant sweep matches N serial static-model runs on every
    (circuit, recipe, topology) cell, including the per-variant
    `select_best` winners;
  * the whole sweep costs exactly ONE jit trace, and changing only the
    model floats never retriggers tracing (`batch.trace_counts`).

The property suites run under hypothesis when it is installed
(``pip install -e .[test]``); deterministic seeded versions of the same
assertions always run, so the parity contract is enforced either way.
"""

import dataclasses

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
    table2_batch,
    trace_counts,
)
from repro.core.explorer import characterize_recipes, explore_suite
from repro.core.mapping import schedule_stats
from repro.core.sram import (
    SWEEPABLE_FIELDS,
    TOPOLOGY_LIBRARY,
    EnergyModel,
    ModelTable,
    evaluate,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs the test extra
    HAVE_HYPOTHESIS = False

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


def random_workload(rng, n_recipes=5, max_levels=9, max_ops=2000):
    items = []
    for i in range(n_recipes):
        n = int(rng.integers(1, max_levels + 1))
        levels = [
            tuple(int(x) for x in rng.integers(0, max_ops, size=3))
            for _ in range(n)
        ]
        items.append(((str(i),), stats_from_levels(levels)))
    return WorkloadTable.from_stats(items)


def one_circuit(work, topos, model, **kw):
    """``work`` swept as a one-circuit suite by the fused program: an
    `ExplorationGrid` for one `EnergyModel`, a `VariationGrid` for a
    `ModelTable`."""
    suite = SuiteTable.from_workloads({"c": work})
    sg, _ = evaluate_select_suite(suite, topos, model, **kw)
    return sg.variation(0) if isinstance(model, ModelTable) else sg.grid(0)


def suite_grid(suite, topos, model, **kw):
    """The suite's grid from the fused program."""
    return evaluate_select_suite(suite, topos, model, **kw)[0]


def scale_model(base: EnergyModel, k: float) -> EnergyModel:
    """Every sweepable field scaled by ``k`` — a maximally 'different'
    model that still exercises all constants."""
    kw = {}
    for f in SWEEPABLE_FIELDS:
        v = getattr(base, f)
        kw[f] = tuple(x * k for x in v) if isinstance(v, tuple) else v * k
    return dataclasses.replace(base, **kw)


def assert_one_variant_bit_identical(work, topos, model, mode, discipline):
    static = one_circuit(work, topos, model, mode=mode, discipline=discipline)
    sweep = one_circuit(
        work, topos, ModelTable.from_models([model]), mode=mode,
        discipline=discipline,
    )
    assert sweep.n_variants == 1
    np.testing.assert_array_equal(static.cycles, sweep.cycles)
    np.testing.assert_array_equal(
        static.active_macro_cycles, sweep.active_macro_cycles
    )
    np.testing.assert_array_equal(static.fits, sweep.fits)
    for k in METRIC_KEYS:
        a, b = getattr(static, k), getattr(sweep, k)[0]
        assert np.array_equal(a, b), f"{k} not bit-identical"
    assert np.array_equal(static.area_mm2, sweep.area_mm2[0])
    # identical winner, including tie-breaking
    assert static.best_index() == int(sweep.best_indices()[0])
    # and the variant-0 slice is a full ExplorationGrid equal to static
    g0 = sweep.grid(0)
    assert np.array_equal(static.energy_nj, g0.energy_nj)
    assert g0.model == model


# ---------------------------------------------------------------------------
# 1-variant sweep == static path, bit for bit (deterministic seeds)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["physical", "paper"])
@pytest.mark.parametrize("discipline", ["list", "levels"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_one_variant_bit_identical(mode, discipline, seed):
    rng = np.random.default_rng(seed)
    work = random_workload(rng)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    model = scale_model(EnergyModel(), float(rng.uniform(0.3, 3.0)))
    assert_one_variant_bit_identical(work, topos, model, mode, discipline)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        workloads=st.lists(
            st.lists(
                st.tuples(
                    st.integers(0, 1500),
                    st.integers(0, 1500),
                    st.integers(0, 400),
                ),
                min_size=1,
                max_size=8,
            ),
            min_size=1,
            max_size=4,
        ),
        scale=st.floats(0.25, 4.0),
        mode=st.sampled_from(["physical", "paper"]),
        discipline=st.sampled_from(["list", "levels"]),
    )
    def test_property_one_variant_bit_identical(
        workloads, scale, mode, discipline
    ):
        work = WorkloadTable.from_stats(
            [((str(i),), stats_from_levels(lv))
             for i, lv in enumerate(workloads)]
        )
        topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
        model = scale_model(EnergyModel(), scale)
        assert_one_variant_bit_identical(work, topos, model, mode, discipline)

    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(1, 9),
        sigma=st.floats(0.01, 0.5),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property_modeltable_roundtrip(n, sigma, seed):
        table = ModelTable.monte_carlo(n=n, sigma=sigma, seed=seed)
        assert len(table) == n
        # float64 -> EnergyModel -> float64 round-trips exactly
        again = ModelTable.from_models(table.models(), names=table.names)
        for f in dataclasses.fields(EnergyModel):
            np.testing.assert_array_equal(
                getattr(table, f.name), getattr(again, f.name)
            )
        # seeded: same seed reproduces, row 0 is nominal
        assert table.model(0) == EnergyModel()
        table2 = ModelTable.monte_carlo(n=n, sigma=sigma, seed=seed)
        np.testing.assert_array_equal(table.p_ctrl_mw, table2.p_ctrl_mw)

else:  # keep the property suite visible as skips when hypothesis is absent

    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[test])")
    def test_property_one_variant_bit_identical():
        pass

    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[test])")
    def test_property_modeltable_roundtrip():
        pass


# ---------------------------------------------------------------------------
# N-variant sweep == N serial static-model runs (65 x 12 slice)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bar_suite():
    suite = C.benchmark_suite(scale="tiny", only=("bar",))
    cha = {"bar": characterize_recipes(suite["bar"])}  # all 64 recipes + ()
    return suite, cha


def test_variant_winners_match_serial_explore_suite(bar_suite):
    suite, cha = bar_suite
    table = ModelTable.monte_carlo(n=4, sigma=0.15, seed=7)
    res = explore_suite(suite, cha=cha, model_sweep=table)["bar"]
    var = res.variation
    assert var is not None and var.n_variants == 4
    assert res.n_evaluations == 65 * 12  # the acceptance slice
    assert sum(var.winner_share.values()) == pytest.approx(1.0)
    for v in range(4):
        serial = explore_suite(suite, cha=cha, model=table.model(v))["bar"]
        # identical winner implementation...
        assert (serial.best.recipe, serial.best.topo) == var.winners[v]
        # ...and identical energies on every (recipe, topology) cell
        np.testing.assert_array_equal(
            var.grid.energy_nj[v], serial.grid.energy_nj
        )
        np.testing.assert_array_equal(
            var.grid.latency_ns[v], serial.grid.latency_ns
        )
    # the headline best/grid are the nominal variant's
    nominal = explore_suite(suite, cha=cha, model=table.model(0))["bar"]
    assert res.best.metrics.energy_nj == nominal.best.metrics.energy_nj
    assert (res.best.recipe, res.best.topo) == (
        nominal.best.recipe, nominal.best.topo
    )


def test_degenerate_sweep_yield_is_one(bar_suite):
    suite, cha = bar_suite
    em = EnergyModel()
    table = ModelTable.from_models([em] * 5)
    res = explore_suite(
        suite, cha=cha, recipes=[("Ba",), ("Rw",)], model_sweep=table
    )["bar"]
    var = res.variation
    assert var.best_yield == 1.0
    assert var.latency_yield == 1.0
    assert len(set(var.winners)) == 1
    assert var.winner_share == {
        f"{res.best.topo.name}/{','.join(res.best.recipe) or '-'}": 1.0
    }


def test_model_sweep_argument_validation(bar_suite):
    suite, cha = bar_suite
    table = ModelTable.corners()
    with pytest.raises(ValueError, match="either model or model_sweep"):
        explore_suite(suite, cha=cha, model=EnergyModel(), model_sweep=table)
    with pytest.raises(ValueError, match="backend"):
        explore_suite(suite, cha=cha, model_sweep=table, backend="python")


# ---------------------------------------------------------------------------
# Compile-count guard: one trace per sweep, zero per float change
# ---------------------------------------------------------------------------


def test_sweep_traces_exactly_once_and_float_changes_do_not_retrace():
    # Unique grid shape (R=11 recipes, C=3 circuits) so the first call is
    # guaranteed to be a fresh trace even when other tests ran first.
    rng = np.random.default_rng(123)
    work = random_workload(rng, n_recipes=11)
    suite = SuiteTable.from_workloads(
        {"a": work, "b": work, "c": work}
    )
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table_a = ModelTable.monte_carlo(n=8, sigma=0.1, seed=0)

    before = trace_counts().get("fused_suite", 0)
    svg_a = suite_grid(suite, topos, table_a)
    assert trace_counts().get("fused_suite", 0) == before + 1

    # Same shapes, different model floats: served from the jit cache.
    table_b = ModelTable.monte_carlo(n=8, sigma=0.4, seed=99)
    svg_b = suite_grid(suite, topos, table_b)
    assert trace_counts().get("fused_suite", 0) == before + 1
    # ...and the floats really flowed through (not a stale constant).
    assert not np.array_equal(svg_a.energy_nj, svg_b.energy_nj)
    np.testing.assert_array_equal(svg_a.cycles, svg_b.cycles)

    # A new variant count is a new shape: exactly one more trace.
    suite_grid(suite, topos, ModelTable.monte_carlo(n=16, seed=1))
    assert trace_counts().get("fused_suite", 0) == before + 2


def test_serial_static_models_share_one_compile():
    rng = np.random.default_rng(321)
    work = random_workload(rng, n_recipes=13)  # unique shape again
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.monte_carlo(n=6, sigma=0.2, seed=5)

    before = trace_counts().get("fused_suite", 0)
    grids = [
        one_circuit(work, topos, table.model(v)) for v in range(6)
    ]
    # the old engine paid one compile per EnergyModel; now the first call
    # traces and the other five hit the cache
    assert trace_counts().get("fused_suite", 0) == before + 1
    # parity of the serial runs against the one-call sweep
    sweep = one_circuit(work, topos, table)
    assert trace_counts().get("fused_suite", 0) == before + 2  # V=6 shape
    for v, g in enumerate(grids):
        np.testing.assert_array_equal(sweep.energy_nj[v], g.energy_nj)
        assert int(sweep.best_indices()[v]) == g.best_index()


# ---------------------------------------------------------------------------
# ModelTable generators
# ---------------------------------------------------------------------------


def test_corners_generator():
    table = ModelTable.corners(spread=0.1)
    assert table.names == ("tt", "ff", "ss")
    base = EnergyModel()
    assert table.model(0) == base
    ff, ss = table.model(1), table.model(2)
    # fast silicon: cheaper ops, faster clock; slow: the reverse
    assert ff.e_op_fj[0] < base.e_op_fj[0] < ss.e_op_fj[0]
    assert ff.f_clk_hz > base.f_clk_hz > ss.f_clk_hz
    # geometry is corner-independent
    assert ff.bitcell_um2 == base.bitcell_um2 == ss.bitcell_um2


def test_sensitivity_generator():
    fields = ("p_ctrl_mw", "e_op_marginal_fj")
    table = ModelTable.sensitivity(fields=fields, rel=0.05)
    assert len(table) == 1 + 2 * len(fields)
    assert table.model(0) == EnergyModel()
    plus = table.model(1)
    assert plus.p_ctrl_mw == pytest.approx(EnergyModel().p_ctrl_mw * 1.05)
    # one-at-a-time: the other field stays nominal
    assert plus.e_op_marginal_fj == EnergyModel().e_op_marginal_fj
    with pytest.raises(ValueError, match="not sweepable"):
        ModelTable.sensitivity(fields=("nonsense",))


def test_monte_carlo_generator_errors_and_fields():
    with pytest.raises(ValueError):
        ModelTable.monte_carlo(n=0)
    with pytest.raises(ValueError):
        ModelTable.from_models([])
    table = ModelTable.monte_carlo(
        n=4, sigma=0.2, seed=11, fields=("f_clk_hz",)
    )
    base = EnergyModel()
    for v in range(1, 4):
        m = table.model(v)
        assert m.f_clk_hz != base.f_clk_hz
        assert m.p_ctrl_mw == base.p_ctrl_mw  # unswept fields untouched


def test_monte_carlo_clamps_utilization():
    # regression: N(1, sigma) at large sigma used to push samples past
    # 1.0 ops per cycle slot, inflating throughput for those variants
    table = ModelTable.monte_carlo(n=64, sigma=2.0, seed=3)
    assert table.pipeline_utilization.max() <= 1.0
    assert table.pipeline_utilization.min() > 0.0
    # the floor still applies to every other field
    assert (table.p_ctrl_mw > 0).all()


def test_empty_model_table_raises():
    # constructing a 0-row table is rejected outright...
    with pytest.raises(ValueError, match="empty ModelTable"):
        ModelTable(
            names=(),
            **{
                f.name: np.zeros((0, 3) if f.name in
                                 ("e_op_fj", "e_op_marginal_fj") else (0,))
                for f in dataclasses.fields(EnergyModel)
            },
        )
    # ...and a degenerate falsy table smuggled past __post_init__ errors
    # loudly instead of being silently swapped for the nominal model by
    # a truthiness check (ModelTable defines __len__)
    rogue = object.__new__(ModelTable)
    object.__setattr__(rogue, "names", ())
    for f in dataclasses.fields(EnergyModel):
        shape = (0, 3) if f.name in ("e_op_fj", "e_op_marginal_fj") else (0,)
        object.__setattr__(rogue, f.name, np.zeros(shape))
    assert not rogue  # falsy: the old `model or EnergyModel()` dropped it
    tt = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:3])
    with pytest.raises(ValueError, match="empty ModelTable"):
        table2_batch(tt, rogue)
    with pytest.raises(ValueError, match="empty ModelTable"):
        one_circuit(random_workload(np.random.default_rng(0)), tt, rogue)


# ---------------------------------------------------------------------------
# Correlated (V, T) variation: per-topology model fields
# ---------------------------------------------------------------------------


def as_v1_table(table: ModelTable) -> ModelTable:
    """The same table with every scalar field reshaped (V,) -> (V, 1)."""
    kw = {}
    for f in dataclasses.fields(EnergyModel):
        arr = getattr(table, f.name)
        if f.name not in ("e_op_fj", "e_op_marginal_fj"):
            arr = arr[:, None]
        kw[f.name] = arr
    return ModelTable(names=table.names, **kw)


def test_v1_table_bit_identical_to_uniform_sweep():
    rng = np.random.default_rng(17)
    work = random_workload(rng)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.monte_carlo(n=4, sigma=0.2, seed=9)
    v1 = as_v1_table(table)
    assert v1.n_topologies is None  # (V, 1) broadcasts uniformly
    a = one_circuit(work, topos, table)
    b = one_circuit(work, topos, v1)
    for k in METRIC_KEYS:
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    np.testing.assert_array_equal(a.area_mm2, b.area_mm2)
    np.testing.assert_array_equal(a.best_indices(), b.best_indices())
    # table2 and the (V, 1) model() round-trip agree too
    tb_a, tb_b = table2_batch(topos, table), table2_batch(topos, v1)
    for k in tb_a:
        np.testing.assert_array_equal(tb_a[k], tb_b[k])
    assert v1.model(2) == table.model(2)


def test_correlated_generator_shapes_and_validation():
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=4, sigma=0.2, seed=0
    )
    assert table.n_topologies == len(TOPOLOGY_LIBRARY)
    assert table.bitcell_um2.shape == (4, 12)
    assert table.f_clk_hz.shape == (4,)  # unswept fields stay (V,)
    assert table.model(0) == EnergyModel()  # row 0 nominal (uniform)
    # smaller macros see a wider spread (Pelgrom-style area averaging):
    # column 0 is (256x128), column 11 is (256x1024)
    spread = table.bitcell_um2[1:].std(axis=0)
    assert spread[0] > spread[9]
    # per-op fields produce a (V, T, 3) axis (tests/test_fused.py covers
    # their kernel parity); unknown fields are rejected
    per_op = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=3, fields=("e_op_fj",)
    )
    assert per_op.e_op_fj.shape == (3, 12, 3)
    assert per_op.n_topologies == 12
    with pytest.raises(ValueError, match="not sweepable"):
        ModelTable.bitcell_sigma_per_macro(
            TOPOLOGY_LIBRARY, fields=("nonsense",)
        )
    with pytest.raises(ValueError, match="empty topology"):
        ModelTable.bitcell_sigma_per_macro(())
    # utilization swept per-topology is clamped like monte_carlo's
    big = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=32, sigma=3.0, seed=1,
        fields=("pipeline_utilization",),
    )
    assert big.pipeline_utilization.max() <= 1.0
    # a mismatched per-topology axis is rejected by the batched paths
    short = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:5])
    table_12 = ModelTable.bitcell_sigma_per_macro(TOPOLOGY_LIBRARY, n=2)
    with pytest.raises(ValueError, match="per-topology axis"):
        one_circuit(
            random_workload(np.random.default_rng(0)), short, table_12
        )
    with pytest.raises(ValueError, match="per-topology axis"):
        table2_batch(short, table_12)
    # ...and so is a same-length but reordered/different topology list,
    # where each column's variation would land on the wrong geometry
    assert table_12.topology_names == tuple(
        t.name for t in TOPOLOGY_LIBRARY
    )
    reordered = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[::-1])
    with pytest.raises(ValueError, match="generated for"):
        one_circuit(
            random_workload(np.random.default_rng(0)), reordered, table_12
        )
    with pytest.raises(ValueError, match="generated for"):
        table2_batch(reordered, table_12)
    # mixed widths inside one table are rejected at construction
    bad_kw = {
        f.name: getattr(table_12, f.name)
        for f in dataclasses.fields(EnergyModel)
    }
    bad_kw["p_ctrl_mw"] = np.ones((2, 5))
    with pytest.raises(ValueError, match="per-topology width"):
        ModelTable(names=table_12.names, **bad_kw)


def test_correlated_variant_slices_work_without_scalar_model():
    """grid(v)/suite(v) slices of a correlated sweep stay usable for
    every variant; only the scalar-model materialization (which is
    genuinely ill-defined per topology-dependent variant) raises."""
    rng = np.random.default_rng(3)
    work = random_workload(rng, n_recipes=3)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=3, sigma=0.3, seed=4
    )
    assert table.uniform_row(0) and not table.uniform_row(1)
    vg = one_circuit(work, topos, table)
    g0, g1 = vg.grid(0), vg.grid(1)
    assert g0.model == EnergyModel()
    assert g1.model is None  # no single EnergyModel represents row 1
    # the slice still filters/selects like any grid
    assert g1.best_index() == int(vg.best_indices()[1])
    assert g1.fit_energies().size > 0
    suite = SuiteTable.from_workloads({"a": work, "b": work})
    svg = suite_grid(suite, topos, table)
    assert svg.suite(0).model == EnergyModel()
    assert svg.suite(1).model is None
    # best_worst needs a scalar model to materialize Evaluations: clear
    # error instead of silently evaluating with the wrong constants
    from repro.core.explorer import ExplorationResult, best_worst

    res = ExplorationResult(
        circuit="x", best=None, inductor_nh=0.0, opt_gate_recipe=(),
        opt_level_recipe=(), evaluations=[], n_recipes=1, wall_s=0.0,
        backend="jax", grid=g1, cha={},
    )
    with pytest.raises(ValueError, match="no single scalar model"):
        best_worst(res)


def test_correlated_sweep_matches_scalar_path():
    """Every (variant, topology) cell of a correlated sweep equals the
    scalar path run with that cell's materialized EnergyModel — the
    same parity contract (rtol 1e-12) as the uniform grids."""
    rng = np.random.default_rng(5)
    items = [
        ((str(i),), stats_from_levels(
            [tuple(int(x) for x in rng.integers(0, 800, 3))
             for _ in range(int(rng.integers(1, 6)))]
        ))
        for i in range(4)
    ]
    work = WorkloadTable.from_stats(items)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=3, sigma=0.4, seed=21
    )
    vg = one_circuit(work, topos, table)
    for v in range(3):
        for t in range(len(TOPOLOGY_LIBRARY)):
            m = table.model(v, topology=t)
            topo = TOPOLOGY_LIBRARY[t]
            for r, (_, stats) in enumerate(items):
                sched = schedule_stats(stats, topo)
                met = evaluate(sched, topo, m)
                np.testing.assert_allclose(
                    vg.energy_nj[v, t, r], met.energy_nj, rtol=1e-12
                )
                np.testing.assert_allclose(
                    vg.latency_ns[v, t, r], met.latency_ns, rtol=1e-12
                )
                np.testing.assert_allclose(
                    vg.throughput_gops[v, t, r], met.throughput_gops,
                    rtol=1e-12,
                )
                np.testing.assert_allclose(
                    vg.area_mm2[v, t], met.area_mm2, rtol=1e-12
                )


def test_suite_best_indices_match_select_best_loop(bar_suite):
    """Acceptance: the batched (C, V) selection pass returns bit-identical
    winners to the per-variant `select_best` loop across every generator
    on the full 65-recipe x 12-topology suite."""
    suite, cha = bar_suite
    suite_table = SuiteTable.from_cha(cha)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    tables = {
        "corners": ModelTable.corners(spread=0.15),
        "sensitivity": ModelTable.sensitivity(rel=0.1),
        "monte_carlo": ModelTable.monte_carlo(n=7, sigma=0.3, seed=13),
        "correlated": ModelTable.bitcell_sigma_per_macro(
            TOPOLOGY_LIBRARY, n=7, sigma=0.3, seed=13
        ),
    }
    for max_lat in (None, 40.0):
        for kind, table in tables.items():
            svg = suite_grid(suite_table, topos, table)
            assert svg.energy_nj.shape[2:] == (12, 65)
            got = svg.best_indices(max_lat)
            assert got.shape == (len(svg.circuits), len(table))
            for c, name in enumerate(svg.circuits):
                vgrid = svg.variation(name)
                feas = np.broadcast_to(
                    vgrid.feasible[:, None], vgrid.fits.shape
                )
                for v in range(len(table)):
                    ref = select_best(
                        vgrid.energy_nj[v], vgrid.fits,
                        latency=vgrid.latency_ns[v], max_latency=max_lat,
                        feasible=feas,
                    )
                    assert int(got[c, v]) == ref, (kind, max_lat, name, v)


def test_variation_cell_matches_materialized_grids(bar_suite):
    """`cell()` on the variation grids — lazy per-design gathers equal
    the materialized tensors field for field, variant axis included."""
    suite, cha = bar_suite
    suite_table = SuiteTable.from_cha(cha)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    table = ModelTable.monte_carlo(n=5, sigma=0.2, seed=17)
    svg = suite_grid(suite_table, topos, table)
    v, t, r = 3, 7, 11
    cell = svg.cell("bar", v, t, r)
    assert cell.circuit == "bar" and cell.variant == v
    assert cell.cycles == int(svg.cycles[0, t, r])
    assert cell.fits == bool(svg.fits[0, t, r])
    assert cell.feasible == bool(svg.feasible[0, t])
    assert cell.energy_nj == float(svg.energy_nj[0, v, t, r])
    assert cell.power_mw == float(svg.power_mw[0, v, t, r])
    assert cell.tops_per_watt == float(svg.tops_per_watt[0, v, t, r])
    assert cell.area_mm2 == float(svg.area_mm2[v, t])
    vg = svg.variation("bar")
    vcell = vg.cell(v, t, r)
    assert vcell.energy_nj == cell.energy_nj
    assert vcell.area_mm2 == cell.area_mm2
    assert vcell.circuit is None and vcell.variant == v


def test_correlated_explore_suite_end_to_end(bar_suite):
    """Acceptance: a (V, T) correlated sweep through
    `explore_suite(model_sweep=...)` -> yield summary, in ONE compile
    (of the fused evaluate+select kernel — the default device-resident
    path since the selection stage moved on device)."""
    suite, cha = bar_suite
    table = ModelTable.bitcell_sigma_per_macro(
        TOPOLOGY_LIBRARY, n=5, sigma=0.5, seed=2
    )
    before = trace_counts().get("fused_suite", 0)
    res = explore_suite(suite, cha=cha, model_sweep=table)["bar"]
    assert trace_counts().get("fused_suite", 0) == before + 1
    var = res.variation
    assert var is not None and var.n_variants == 5
    assert res.n_evaluations == 65 * 12
    assert sum(var.winner_share.values()) == pytest.approx(1.0)
    assert 0.0 < var.best_yield <= 1.0
    # winners equal the per-variant loop on the circuit's VariationGrid
    feas = np.broadcast_to(var.grid.feasible[:, None], var.grid.fits.shape)
    for v, (recipe, topo) in enumerate(var.winners):
        ti, ri = var.grid.unravel(
            select_best(
                var.grid.energy_nj[v], var.grid.fits,
                latency=var.grid.latency_ns[v], feasible=feas,
            )
        )
        assert (var.grid.recipes[ri], var.grid.topologies[ti]) == (
            recipe, topo
        )
    # headline best stays the nominal variant's
    nominal = explore_suite(suite, cha=cha, model=table.model(0))["bar"]
    assert (res.best.recipe, res.best.topo) == (
        nominal.best.recipe, nominal.best.topo
    )


# ---------------------------------------------------------------------------
# Vectorized area + Table II over the model axis
# ---------------------------------------------------------------------------


def test_topology_table_area_vectorized_matches_scalar():
    tt = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    em = EnergyModel()
    ref = np.array([t.area_mm2(em) for t in TOPOLOGY_LIBRARY])
    np.testing.assert_array_equal(tt.area_mm2(em), ref)

    table = ModelTable.sensitivity(
        fields=("bitcell_um2", "periphery_overhead"), rel=0.1
    )
    va = tt.area_mm2(table)
    assert va.shape == (len(table), len(TOPOLOGY_LIBRARY))
    for v in range(len(table)):
        np.testing.assert_array_equal(
            va[v],
            np.array([t.area_mm2(table.model(v)) for t in TOPOLOGY_LIBRARY]),
        )


def test_table2_batch_over_model_table():
    tt = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:5])
    table = ModelTable.monte_carlo(n=3, sigma=0.1, seed=2)
    out = table2_batch(tt, table)
    for v in range(3):
        ref = table2_batch(tt, table.model(v))
        for k, arr in ref.items():
            assert out[k].shape == (3, 5)
            np.testing.assert_array_equal(out[k][v], arr)
