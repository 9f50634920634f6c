"""Transform tests: the four ABC-style transforms preserve semantics.

Deterministic equivalence / regression tests always run; the
hypothesis-driven property tests are gated on the optional dependency
(``pip install -e .[test]``) instead of skipping the whole module.
"""

import numpy as np
import pytest

from repro.core import circuits as C
from repro.core import transforms as T
from repro.core.aig import Aig, lit, random_aig
from repro.core.transforms import (
    RecipeRunner,
    _cofactors,
    _cover_tt,
    _enumerate_cuts,
    _isop,
    _mffc_size,
    _mffc_sizes,
    _reconv_cut,
    _tt_mask,
    apply_recipe,
    balance,
    enumerate_recipes,
    refactor,
    resub,
    rewrite,
    synth_plan,
    build_plan,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs the test extra
    HAVE_HYPOTHESIS = False

TRANSFORMS = [balance, rewrite, refactor, resub]
rng = np.random.default_rng(42)


def equivalent(a, b, n_words=8) -> bool:
    if a.n_pis != b.n_pis or len(a.pos) != len(b.pos):
        return False
    pv = rng.integers(0, 1 << 63, size=(a.n_pis, n_words), dtype=np.int64).astype(np.uint64)
    return np.array_equal(a.simulate(pv), b.simulate(pv))


def exhaustive_equivalent(a, b) -> bool:
    """Exact check for <= 10 PIs via all input patterns."""
    from repro.core.aig import _elementary_tables

    k = a.n_pis
    assert k <= 10
    pv = _elementary_tables(k)
    words = pv.shape[1]
    return np.array_equal(a.simulate(pv), b.simulate(pv))


@pytest.mark.parametrize("fn", TRANSFORMS)
@pytest.mark.parametrize(
    "gen", [lambda: C.gen_adder(16), lambda: C.gen_multiplier(8),
            lambda: C.gen_max(8, 4), lambda: C.gen_sine(8)],
    ids=["adder16", "mult8", "max8", "sine8"],
)
def test_transform_on_circuits(fn, gen):
    a = gen()
    b = fn(a)
    assert equivalent(a, b), fn.__name__
    assert b.n_ands <= a.n_ands * 1.05 + 4  # never blows up


def test_recipe_count():
    rs = enumerate_recipes()
    assert len(rs) == 64  # sum_{i=1..4} P(4,i) = 4+12+24+24
    assert len(set(rs)) == 64


def test_recipe_prefix_cache_consistent():
    a = C.gen_adder(12)
    runner = RecipeRunner(a)
    direct = apply_recipe(a, ("Ba", "Rw", "Rs"))
    cached = runner.run(("Ba", "Rw", "Rs"))
    # same prefix path -> identical results from the runner
    assert equivalent(direct, cached)
    assert equivalent(a, cached)


def test_all_recipes_equivalent_small():
    a = C.gen_max(6, 3)
    runner = RecipeRunner(a)
    for r in enumerate_recipes():
        assert exhaustive_equivalent(a, runner.run(r)) if a.n_pis <= 10 else equivalent(a, runner.run(r)), r


def test_rewrite_reduces_redundant():
    a = random_aig(8, 300, 4, seed=9)
    b = rewrite(a)
    assert b.n_ands <= a.n_ands


# ------------------------- sparse cone pairs --------------------------------


def _transform_queries(a):
    """The (root, leaves) queries of the device rewrite (every k-cut) and
    refactor (reconvergence cuts) on ``a``."""
    cuts = _enumerate_cuts(a, k=4, max_cuts=8)
    rw = [(n, sorted(c)) for n in range(a.n_pis + 1, a.n_nodes)
          for c in cuts[n] if len(c) >= 2 and n not in c]
    rf = [(n, _reconv_cut(a, n, 10)) for n in range(a.n_pis + 1, a.n_nodes, 3)]
    return rw + [(n, l) for n, l in rf if n not in l]


def _reconvergent():
    """r = (x & c) & (x & !c) over x = a & b: x feeds the cone twice."""
    a = Aig(3)
    x = a.g_and(lit(1), lit(2))
    r = a.g_and(a.g_and(x, lit(3)), a.g_and(x, lit(3) ^ 1))
    a.add_po(r)
    a.add_po(x)  # x also leaves the cone, so it is not freed
    return a, [(r >> 1, [1, 2, 3]), (r >> 1, [x >> 1, 3])]


def _root_alone():
    """Each AND node cut at its own two fanins: the root is the cone."""
    a = C.gen_adder(4)
    return a, [(n, sorted({f >> 1 for f in a.fanins(n)}))
               for n in range(a.n_pis + 1, a.n_nodes)]


def _pi_leaves():
    """Cones cut at every primary input reach down to the inputs."""
    a = random_aig(5, 40, 2, seed=3)
    pis = list(range(1, a.n_pis + 1))
    return a, [(n, pis) for n in range(a.n_pis + 1, a.n_nodes)]


CONE_CASES = {
    "random0": lambda: (a := random_aig(6, 80, 3, seed=0), _transform_queries(a)),
    "random1": lambda: (a := random_aig(8, 200, 4, seed=1), _transform_queries(a)),
    "adder8": lambda: (a := C.gen_adder(8), _transform_queries(a)),
    "sine6": lambda: (a := C.gen_sine(6), _transform_queries(a)),
    "max6": lambda: (a := C.gen_max(6, 4), _transform_queries(a)),
    "sqrt8": lambda: (a := C.gen_sqrt(8), _transform_queries(a)),
    "reconvergent": _reconvergent,
    "root_alone": _root_alone,
    "pi_leaves": _pi_leaves,
}


@pytest.mark.parametrize("case", sorted(CONE_CASES))
def test_cone_pairs_match_cone_nodes(case):
    """`Aig.cone_pairs` gives each query's `cone_nodes`, and `_mffc_sizes`
    its `_mffc_size`, query by query."""
    a, queries = CONE_CASES[case]()
    assert queries
    roots = [n for n, _ in queries]
    b_idx, node_idx, rounds = a.cone_pairs([(n,) for n in roots], [l for _, l in queries],
                                           a.levels())
    assert np.all(np.diff(b_idx * a.n_nodes + node_idx) > 0)  # sorted, no repeats
    fanout = a.fanout_counts()
    sizes = _mffc_sizes(a, roots, b_idx, node_idx, fanout)
    bounds = np.searchsorted(b_idx, np.arange(len(queries) + 1))
    for i, (n, leaves) in enumerate(queries):
        cone = a.cone_nodes(n, set(leaves))
        assert node_idx[bounds[i]:bounds[i + 1]].tolist() == sorted(cone), (n, leaves)
        assert sizes[i] == _mffc_size(a, n, frozenset(leaves), fanout, cone=cone), (n, leaves)
    assert rounds >= 1
    if case == "reconvergent":
        assert sizes.tolist() == [3, 3]  # x stays: a PO reads it
    if case == "root_alone":
        assert np.array_equal(node_idx, roots) and np.all(sizes == 1)


def test_cone_pairs_union_cones_feed_eval_tts():
    """resub's (n, m) queries: the pairs of a two-root union cone, handed
    to `eval_tts` on the jnp engine, give the python truth tables."""
    from repro.kernels import aig_sim

    a = random_aig(7, 120, 3, seed=5)
    supports = T._supports(a, cap=7)
    ands = [n for n in range(a.n_pis + 1, a.n_nodes) if supports[n] is not None]
    items = []
    for n, m in zip(ands[::3], ands[1::3]):
        sup = sorted(supports[n] | supports[m])
        items.append(((lit(n), lit(m, 1)), sup))
    assert len(items) >= 4
    b_idx, node_idx, _ = a.cone_pairs([[r >> 1 for r in roots] for roots, _ in items],
                                      [s for _, s in items], a.levels())
    bounds = np.searchsorted(b_idx, np.arange(len(items) + 1))
    grown = 0
    for i, (roots, sup) in enumerate(items):
        cones = [set(a.cone_nodes(r >> 1, set(sup))) for r in roots]
        union = cones[0] | cones[1]
        assert node_idx[bounds[i]:bounds[i + 1]].tolist() == sorted(union)
        grown += len(union) > max(map(len, cones))
    assert grown  # some union is wider than either cone
    got = aig_sim.eval_tts(a, items, engine="jnp", members=(b_idx, node_idx))
    assert got == [tuple(a.truth_table(r, sup) for r in roots) for roots, sup in items]


# ------------------------- property tests (hypothesis) ---------------------


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        n_pis=st.integers(4, 9),
        n_ands=st.integers(10, 150),
        n_pos=st.integers(1, 6),
        seed=st.integers(0, 10**6),
        which=st.integers(0, 3),
    )
    def test_transform_preserves_function_exact(n_pis, n_ands, n_pos, seed, which):
        a = random_aig(n_pis, n_ands, n_pos, seed=seed)
        b = TRANSFORMS[which](a)
        assert exhaustive_equivalent(a, b), TRANSFORMS[which].__name__

    @settings(max_examples=80, deadline=None)
    @given(k=st.integers(1, 7), tt=st.integers(0, 2**63 - 1), i=st.integers(0, 6))
    def test_cofactors_brute(k, tt, i):
        if i >= k:
            i = i % k
        tt &= _tt_mask(k)
        neg, pos = _cofactors(tt, i, k)
        bneg = bpos = 0
        for p in range(1 << k):
            bpos |= ((tt >> (p | (1 << i))) & 1) << p
            bneg |= ((tt >> (p & ~(1 << i))) & 1) << p
        assert (neg, pos) == (bneg, bpos)

    @settings(max_examples=80, deadline=None)
    @given(k=st.integers(1, 7), tt=st.integers(0, 2**63 - 1))
    def test_isop_covers_exactly(k, tt):
        tt &= _tt_mask(k)
        cubes = _isop(tt, _tt_mask(k), k)
        assert _cover_tt(cubes, k) == tt

    @settings(max_examples=60, deadline=None)
    @given(k=st.integers(1, 4), tt=st.integers(0, 2**16 - 1))
    def test_synth_plan_correct(k, tt):
        from repro.core.aig import Aig, lit

        tt &= _tt_mask(k)
        cost, plan = synth_plan(tt, k)
        aig = Aig(k)
        out = build_plan(aig, plan, [lit(i + 1) for i in range(k)])
        aig.add_po(out)
        got = aig.truth_table(out, list(range(1, k + 1)))
        assert got == tt
        assert cost >= 0

else:  # pragma: no cover - CI installs the test extra

    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[test])")
    def test_property_transforms():
        pass
