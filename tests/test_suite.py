"""Suite-level engine tests: the prefix-DAG runner, the persistent
characterization cache, and the circuits x recipes x topologies sweep.

Contracts under test:

  * the deduped prefix-DAG runner produces byte-identical AIG stats to
    independent per-recipe transform chains;
  * the on-disk cache hits, misses, and invalidates on a
    `TRANSFORM_VERSION` bump;
  * `SuiteTable` padding/masking is invisible: suite results equal each
    circuit run as its own one-circuit suite on the full 65 x 12 grid;
  * the programmatic topology grid schedules/evaluates exactly like the
    scalar path.
"""

import numpy as np
import pytest

from repro.core import circuits as C
from repro.core import transforms as T
from repro.core.aig import AigStats
from repro.core.batch import (
    LEVEL_PAD,
    SuiteTable,
    TopologyTable,
    WorkloadTable,
    evaluate_select_suite,
)
from repro.core.explorer import explore, explore_suite
from repro.core.mapping import macros_per_type, schedule_stats
from repro.core.sram import (
    TOPOLOGY_LIBRARY,
    EnergyModel,
    SramTopology,
    topology_grid,
)
from repro.core.transforms import (
    CharacterizationCache,
    RecipeRunner,
    characterize_suite,
    enumerate_recipes,
    prefix_nodes,
)

EM = EnergyModel()


@pytest.fixture(scope="module")
def tiny_pair():
    """Two small circuits with different level structures."""
    return {
        "bar-16": C.gen_barrel_shifter(16),
        "sqrt-8": C.gen_sqrt(8),
    }


@pytest.fixture(scope="module")
def tiny_cha(tiny_pair):
    return characterize_suite(tiny_pair, n_jobs=1)


# ---------------------------------------------------------------------------
# Prefix-DAG runner
# ---------------------------------------------------------------------------


SAMPLE_RECIPES = [
    ("Ba",), ("Rf",), ("Rw",), ("Rs",),
    ("Rw", "Ba"), ("Rf", "Rw"), ("Rs", "Rw", "Ba"),
    ("Ba", "Rf", "Rw", "Rs"), ("Rs", "Rw", "Rf", "Ba"),
]


def test_prefix_dag_byte_identical_to_independent_runs(tiny_pair, tiny_cha):
    """Structural dedup must be invisible: each recipe's stats equal an
    independent no-sharing transform chain's."""
    for name, rtl in tiny_pair.items():
        for recipe in SAMPLE_RECIPES:
            a = rtl
            for t in recipe:
                a = T._TRANSFORM_FNS[t](a)
            assert a.characterize() == tiny_cha[name][recipe], (name, recipe)


def test_recipe_runner_dedups_structurally():
    rtl = C.gen_adder(32)
    runner = RecipeRunner(rtl)
    recipes = enumerate_recipes()
    for r in recipes:
        runner.run(r)
    # prefix sharing alone caps at 64; structural dedup must do better
    assert runner.n_applied <= 64
    assert runner.n_applied < len(prefix_nodes(recipes))
    # stats memoized per distinct structure, identical across aliases
    s1 = runner.stats(("Ba", "Rw"))
    s2 = RecipeRunner(rtl).stats(("Ba", "Rw"))
    assert s1 == s2


def test_prefix_nodes_order():
    nodes = prefix_nodes([("Ba", "Rf"), ("Rf",)])
    assert nodes == [("Ba",), ("Rf",), ("Ba", "Rf")]
    assert prefix_nodes([]) == []


def test_characterize_suite_parallel_matches_serial(tiny_pair):
    # include deep chains so the as-completed scheduler's cascade path
    # (resolve -> children -> submit) is exercised, not just the roots
    few = enumerate_recipes()[:6] + [
        ("Ba", "Rf", "Rw", "Rs"), ("Rs", "Rw", "Rf", "Ba"),
        ("Rw", "Ba", "Rs"),
    ]
    serial = characterize_suite(tiny_pair, few, n_jobs=1)
    parallel = characterize_suite(tiny_pair, few, n_jobs=2)
    assert serial == parallel


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


def test_cache_miss_then_hit(tmp_path, tiny_pair):
    cache = CharacterizationCache(tmp_path)
    few = enumerate_recipes()[:4]
    first = characterize_suite(tiny_pair, few, cache=cache, n_jobs=1)
    assert cache.misses == len(tiny_pair) and cache.hits == 0
    vdir = tmp_path / f"v{T.TRANSFORM_VERSION}"
    stats_files = [
        p for p in vdir.glob("*.json") if not p.name.endswith(".apps.json")
    ]
    assert len(stats_files) == len(tiny_pair)
    # per-prefix application persistence rides alongside the stats files
    apps_files = list(vdir.glob("*.apps.json"))
    assert len(apps_files) == len(tiny_pair)

    second = characterize_suite(tiny_pair, few, cache=cache, n_jobs=1)
    assert cache.hits == len(tiny_pair)
    assert first == second

    # a path (str) is accepted in place of a CharacterizationCache
    third = characterize_suite(tiny_pair, few, cache=str(tmp_path), n_jobs=1)
    assert first == third


def test_cache_partial_covers_superset(tmp_path, tiny_pair):
    """A cache warmed with a recipe subset must recompute (and then serve)
    a superset request."""
    cache = CharacterizationCache(tmp_path)
    few = enumerate_recipes()[:2]
    more = enumerate_recipes()[:5]
    characterize_suite(tiny_pair, few, cache=cache, n_jobs=1)
    full = characterize_suite(tiny_pair, more, cache=cache, n_jobs=1)
    assert cache.misses == 2 * len(tiny_pair)  # second call missed too
    again = characterize_suite(tiny_pair, more, cache=cache, n_jobs=1)
    assert again == full
    assert cache.hits == len(tiny_pair)


def test_cache_invalidated_on_version_bump(tmp_path, tiny_pair, monkeypatch):
    cache = CharacterizationCache(tmp_path)
    few = enumerate_recipes()[:3]
    characterize_suite(tiny_pair, few, cache=cache, n_jobs=1)
    assert cache.misses == len(tiny_pair)

    monkeypatch.setattr(T, "TRANSFORM_VERSION", T.TRANSFORM_VERSION + 1)
    bumped = CharacterizationCache(tmp_path)
    characterize_suite(tiny_pair, few, cache=bumped, n_jobs=1)
    assert bumped.misses == len(tiny_pair) and bumped.hits == 0
    # stale and fresh version directories coexist
    assert (tmp_path / f"v{T.TRANSFORM_VERSION}").is_dir()


def test_cache_rejects_stale_embedded_version(tmp_path, tiny_pair, monkeypatch):
    """A file whose embedded version disagrees with its directory (e.g. a
    hand-copied cache) is treated as a miss, not served."""
    cache = CharacterizationCache(tmp_path)
    few = enumerate_recipes()[:2]
    characterize_suite(tiny_pair, few, cache=cache, n_jobs=1)
    vdir = tmp_path / f"v{T.TRANSFORM_VERSION}"
    for f in vdir.glob("*.json"):
        text = f.read_text().replace(
            f'"transform_version": {T.TRANSFORM_VERSION}',
            '"transform_version": 0',
        )
        f.write_text(text)
    fresh = CharacterizationCache(tmp_path)
    fp = next(iter(tiny_pair.values())).fingerprint()
    assert fresh.load(fp) == {}


def test_aig_stats_roundtrip(tiny_cha):
    for cha in tiny_cha.values():
        for stats in cha.values():
            assert AigStats.from_dict(stats.to_dict()) == stats


# ---------------------------------------------------------------------------
# SuiteTable / evaluate_select_suite parity on the 65 x 12 grid
# ---------------------------------------------------------------------------


def one_circuit_grid(cha, topos, model=EM, **kw):
    """One circuit's recipes swept as a one-circuit suite: its grid."""
    sg, _ = evaluate_select_suite(SuiteTable.from_cha({"c": cha}), topos, model, **kw)
    return sg.grid(0)


@pytest.mark.parametrize("mode", ["physical", "paper"])
@pytest.mark.parametrize("discipline", ["list", "levels"])
def test_suite_matches_per_circuit_grids(tiny_cha, mode, discipline):
    suite = SuiteTable.from_cha(tiny_cha)
    assert suite.ops.shape[:2] == (len(tiny_cha), 65)
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    sg, _ = evaluate_select_suite(suite, topos, EM, mode=mode, discipline=discipline)
    for name, cha in tiny_cha.items():
        ref = one_circuit_grid(cha, topos, mode=mode, discipline=discipline)
        got = sg.grid(name)
        assert np.array_equal(got.cycles, ref.cycles)
        assert np.array_equal(got.active_macro_cycles, ref.active_macro_cycles)
        assert np.array_equal(got.fits, ref.fits)
        for field in ("energy_nj", "latency_ns", "power_mw",
                      "throughput_gops", "tops_per_watt"):
            np.testing.assert_allclose(
                getattr(got, field), getattr(ref, field), rtol=1e-12
            )
        assert got.best_index() == ref.best_index()


def test_suite_padding_is_masked(tiny_cha):
    """Circuits with different level counts share one padded axis; the
    shorter circuit's padded rows must not leak into its schedule."""
    suite = SuiteTable.from_cha(tiny_cha)
    names = list(tiny_cha)
    assert suite.n_levels[0].max() != suite.n_levels[1].max()
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY[:3])
    ss, _ = evaluate_select_suite(suite, topos, EM)
    for i, name in enumerate(names):
        ref = one_circuit_grid(tiny_cha[name], topos)
        assert np.array_equal(ss.cycles[i], ref.cycles)
        assert np.array_equal(ss.fits[i], ref.fits)


def test_suite_table_workload_view(tiny_cha):
    suite = SuiteTable.from_cha(tiny_cha)
    for name in tiny_cha:
        w = suite.workload(name)
        assert w.recipes == suite.recipes
        assert w.gates.tolist() == [
            s.total_gates for s in tiny_cha[name].values()
        ]


def _stats_of_depth(depth: int, seed: int) -> AigStats:
    levels = np.random.default_rng(seed).integers(0, 50, size=(depth, 3))
    ops = [dict(nand=int(a), nor=int(b), inv=int(c)) for a, b, c in levels]
    if depth:
        ops[0].pop("nor")  # a level without an op type reads as zero of it
    return AigStats(
        n_pis=4, n_pos=2, n_ands=0, n_levels=depth, ops_per_level=ops,
        nand_count=int(levels[:, 0].sum()),
        nor_count=int(levels[1:, 1].sum()),
        inv_count=int(levels[:, 2].sum()),
    )


@pytest.mark.parametrize("depths,pad_levels_to", [
    ({"a": [3, 17, 130], "b": [1, 40, 0], "c": [64, 5, 9]}, LEVEL_PAD),
    ({"a": [2 * LEVEL_PAD, 7, 1], "b": [LEVEL_PAD, 3, 12]}, LEVEL_PAD),
    ({"a": [3, 17, 130], "b": [1, 40, 2]}, 100),
    ({"a": [3, 17, 130], "b": [1, 40, 2]}, 1),
], ids=["mixed", "on_pad_multiple", "pad_100", "pad_1"])
def test_suite_table_from_cha_matches_stacked_workloads(depths, pad_levels_to):
    """`from_cha` fills the padded tensor from each record's matrix; it
    equals stacking each circuit's `WorkloadTable.from_stats` through
    `from_workloads`, dtypes and shapes included."""
    recipes = [(), ("Ba",), ("Rw", "Rf")]
    cha = {
        name: {r: _stats_of_depth(d, seed=10 * ci + ri)
               for ri, (r, d) in enumerate(zip(recipes, ds))}
        for ci, (name, ds) in enumerate(depths.items())
    }
    max_l = max(d for ds in depths.values() for d in ds)
    l_pad = -(-max(max_l, 1) // pad_levels_to) * pad_levels_to
    ref = SuiteTable.from_workloads({
        name: WorkloadTable.from_stats(rows, pad_levels_to=l_pad)
        for name, rows in cha.items()
    })
    got = SuiteTable.from_cha(cha, pad_levels_to=pad_levels_to)
    assert got.circuits == ref.circuits == tuple(depths)
    assert got.recipes == ref.recipes == tuple(recipes)
    assert got.ops.shape == (len(cha), len(recipes), l_pad, 3)
    for field in ("ops", "n_levels", "op_totals", "gates"):
        a, b = getattr(got, field), getattr(ref, field)
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert np.array_equal(a, b), field
    assert got.gates.tolist() == [
        [s.total_gates for s in rows.values()] for rows in cha.values()
    ]


def test_suite_table_validation(tiny_cha):
    with pytest.raises(ValueError, match="empty"):
        SuiteTable.from_cha({})
    name = next(iter(tiny_cha))
    lopsided = dict(tiny_cha)
    lopsided["short"] = {(): tiny_cha[name][()]}
    with pytest.raises(ValueError, match="different recipe set"):
        SuiteTable.from_cha(lopsided)
    last = list(tiny_cha)[-1]
    reordered = dict(tiny_cha)
    reordered[last] = dict(reversed(tiny_cha[last].items()))
    with pytest.raises(ValueError, match=f"circuit {last!r} covers a different"):
        SuiteTable.from_cha(reordered)


def test_explore_suite_matches_explore(tiny_pair, tiny_cha):
    res_jax = explore_suite(tiny_pair, cha=tiny_cha, backend="jax")
    res_py = explore_suite(tiny_pair, cha=tiny_cha, backend="python")
    for name, rtl in tiny_pair.items():
        one = explore(rtl, cha=tiny_cha[name], backend="python")
        for res in (res_jax[name], res_py[name]):
            assert res.best.recipe == one.best.recipe
            assert res.best.topo == one.best.topo
            assert abs(res.best.metrics.energy_nj - one.best.metrics.energy_nj) < 1e-9
        assert res_jax[name].grid is not None
        assert res_jax[name].n_evaluations == 65 * 12


def test_cell_matches_materialized_grids(tiny_pair, tiny_cha):
    """`cell()` — the lazy per-design gather — must equal the
    materialized grid entry field for field, on both the per-circuit and
    suite grids."""
    topos = TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    suite = SuiteTable.from_cha(tiny_cha)
    sg, _ = evaluate_select_suite(suite, topos, EM)
    t, r = 3, 5
    for ci, name in enumerate(sg.circuits):
        cell = sg.cell(name, t, r)
        assert cell.circuit == name and cell.variant is None
        assert cell.recipe == sg.recipes[r]
        assert cell.topology == sg.topologies[t]
        assert cell.cycles == int(sg.cycles[ci, t, r])
        assert cell.fits == bool(sg.fits[ci, t, r])
        assert cell.feasible == bool(sg.feasible[ci, t])
        assert cell.energy_nj == float(sg.energy_nj[ci, t, r])
        assert cell.latency_ns == float(sg.latency_ns[ci, t, r])
        assert cell.area_mm2 == float(sg.area_mm2[t])
        # the sliced per-circuit grid agrees with the suite-level gather
        eg = sg.grid(name)
        ecell = eg.cell(t, r)
        assert ecell.energy_nj == cell.energy_nj
        assert ecell.cycles == cell.cycles
        assert sg.cell(ci, t, r) == cell  # index addressing too


# ---------------------------------------------------------------------------
# Programmatic topology grid
# ---------------------------------------------------------------------------


def test_macros_per_type_generalization():
    assert macros_per_type(1) == (1, 1, 1)
    assert macros_per_type(3) == (1, 1, 1)
    assert macros_per_type(6) == (2, 2, 2)
    assert macros_per_type(9) == (3, 3, 3)
    for bad in (0, 2, 4, 5, 7):
        with pytest.raises(ValueError):
            macros_per_type(bad)


def test_from_geometry_and_names():
    t = SramTopology.from_geometry(512, 512, 9)
    assert t.macro_kb == 32 and t.rows == 512 and t.cols == 512
    assert t.name == "(512x512)x9"
    assert t.ops_per_cycle_per_macro == 256
    with pytest.raises(ValueError, match="whole number of KB"):
        SramTopology.from_geometry(100, 100, 1)
    # library entries are untouched by the geometry extension
    t8 = SramTopology(8, 1)
    assert t8.name == "(8KB)x1" and t8.rows == 256 and t8.cols == 256


def test_topology_grid_contents():
    grid = topology_grid()
    assert len(grid) == len(set(grid)) and len(grid) > 12
    for t in grid:
        assert (t.rows * t.cols) % 8192 == 0
        macros_per_type(t.n_macros)  # must not raise
    custom = topology_grid(rows=(256,), cols=(256,), macro_counts=(1, 9))
    assert [t.name for t in custom] == ["(256x256)x1", "(256x256)x9"]
    with pytest.raises(ValueError, match="empty"):
        topology_grid(rows=(100,), cols=(100,))


def test_grid_topology_schedule_matches_scalar(tiny_cha):
    """Custom design points run through the batched path exactly like the
    scalar reference."""
    name = next(iter(tiny_cha))
    cha = tiny_cha[name]
    topos = topology_grid(rows=(128, 512), cols=(256, 512), macro_counts=(1, 3, 9))
    table = TopologyTable.from_topologies(topos)
    grid = one_circuit_grid(cha, table)
    recipes = list(cha)
    for ti, topo in enumerate(topos):
        for ri in (0, len(recipes) // 2, len(recipes) - 1):
            sched = schedule_stats(cha[recipes[ri]], topo)
            assert grid.cycles[ti, ri] == sched.total_cycles
            assert bool(grid.fits[ti, ri]) == sched.fits
