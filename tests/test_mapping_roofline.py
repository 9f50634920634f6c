"""Tests on the rCiM scheduler + roofline HLO parsing.

Deterministic scheduler/parsing tests always run; the hypothesis-driven
property tests are gated on the optional dependency
(``pip install -e .[test]``) instead of skipping the whole module.
"""

import numpy as np
import pytest

from repro.core.aig import AigStats
from repro.core.mapping import schedule_stats
from repro.core.sram import SramTopology

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs the test extra
    HAVE_HYPOTHESIS = False


def stats_from_levels(levels):
    ops = [dict(nand=a, nor=b, inv=c) for a, b, c in levels]
    return AigStats(
        n_pis=8, n_pos=4, n_ands=0, n_levels=len(ops), ops_per_level=ops,
        nand_count=sum(l[0] for l in levels),
        nor_count=sum(l[1] for l in levels),
        inv_count=sum(l[2] for l in levels),
    )


def test_capacity_monotone():
    stats = stats_from_levels([(400, 400, 200)] * 10)
    fits = [schedule_stats(stats, SramTopology(kb, 1)).fits for kb in (4, 8, 16, 32)]
    # once it fits, bigger macros also fit
    assert fits == sorted(fits)


def test_row_budget_gates_feasibility():
    """Regression: a wide-but-shallow netlist whose working set exceeds the
    row budget must NOT report fits=True on bit capacity alone.

    2000 NAND2 in one level on an 8-row x 1024-col macro (1 KB): the
    4-bits/gate rule passes (8000 <= 8192 bits) but the single level
    needs ceil(2000/512) = 4 batches -> 3*4+2 = 14 rows > 8.
    """
    from repro.core.batch import SuiteTable, TopologyTable, evaluate_select_suite
    from repro.core.mapping import BITS_PER_GATE

    starved = SramTopology.from_geometry(8, 1024, 1)
    wide = stats_from_levels([(2000, 0, 0)])
    deep = stats_from_levels([(64, 0, 0)] * 10)  # control: 5 rows suffice
    for disc in ("levels", "list"):
        res = schedule_stats(wide, starved, discipline=disc)
        assert BITS_PER_GATE * wide.total_gates <= starved.total_bits
        assert not res.fits, f"{disc}: row-starved schedule must not fit"
        assert res.rows_used <= starved.rows
        assert schedule_stats(deep, starved, discipline=disc).fits

    # The batched engine applies the identical two-term feasibility check.
    suite = SuiteTable.from_cha({"c": {("wide",): wide, ("deep",): deep}})
    topos = TopologyTable.from_topologies([starved, SramTopology(8, 1)])
    for disc in ("levels", "list"):
        sg, _ = evaluate_select_suite(suite, topos, discipline=disc)
        got = sg.grid(0)
        for ti, topo in enumerate(topos.topologies):
            for ri, st_ in enumerate((wide, deep)):
                ref = schedule_stats(st_, topo, discipline=disc)
                assert bool(got.fits[ti, ri]) == ref.fits


# ------------------------------ roofline parse ------------------------------

FAKE_HLO = """
ENTRY %main {
  %p0 = f32[256,1024]{1,0} parameter(0)
  %ag = f32[256,16384]{1,0} all-gather(%p0), replica_groups=[32,16]<=[512], dimensions={1}
  %ar = f32[256,1024]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%sum
  %rs = bf16[16,1024]{1,0} reduce-scatter(%something), replica_groups=[32,16]<=[512]
  %cp = f32[8,128]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a = f32[64,512]{1,0} all-to-all(%p0), replica_groups=[32,16]<=[512]
  %ar2 = f32[4]{0} all-reduce-done(%ar)
}
"""


def test_collective_parse():
    from repro.launch.roofline import collective_bytes

    stats = collective_bytes(FAKE_HLO, default_group=16)
    kinds = set(stats.by_kind)
    assert kinds == {"all-gather", "all-reduce", "reduce-scatter",
                     "collective-permute", "all-to-all"}
    # all-gather: result 256*16384*4 bytes, n=16 -> 15/16 of result
    ag = 256 * 16384 * 4 * 15 / 16
    assert stats.by_kind["all-gather"] == pytest.approx(ag)
    # all-reduce: group list of 4 -> 2*(3/4)*payload
    ar = 2 * (3 / 4) * 256 * 1024 * 4
    assert stats.by_kind["all-reduce"] == pytest.approx(ar)
    # reduce-scatter: result is one shard -> (n-1)*result
    rs = 15 * 16 * 1024 * 2
    assert stats.by_kind["reduce-scatter"] == pytest.approx(rs)
    assert stats.by_kind["collective-permute"] == pytest.approx(8 * 128 * 4)
    assert stats.n_ops == 5  # -done line not double counted


TUPLE_HLO = """
ENTRY %main {
  %art = (f32[128,256]{1,0}, bf16[64]{0}) all-reduce(%a, %b), replica_groups=[4,8]<=[32], to_apply=%sum
  %agd = f32[32,2048]{1,0} all-gather(%p), channel_id=1, dimensions={1}
  %cp2 = bf16[4,64]{1,0} collective-permute(%p), source_target_pairs={{0,1},{1,2}}
}
"""


def test_collective_parse_tuple_and_default_group():
    from repro.launch.roofline import collective_bytes

    stats = collective_bytes(TUPLE_HLO, default_group=16)
    # variadic all-reduce: every tuple element is payload; n=8 from the
    # iota form [4,8]<=[32] (groups of size 8)
    payload = 128 * 256 * 4 + 64 * 2
    assert stats.by_kind["all-reduce"] == pytest.approx(2 * (7 / 8) * payload)
    # no replica_groups on the line -> the model-axis default group size
    ag = 32 * 2048 * 4
    assert stats.by_kind["all-gather"] == pytest.approx((15 / 16) * ag)
    # collective-permute is group-size independent: 1 x payload
    assert stats.by_kind["collective-permute"] == pytest.approx(4 * 64 * 2)
    assert stats.n_ops == 3


def test_collective_ring_factors_exact():
    """Pin each kind's ring factor on the shared fixture (the all-to-all
    term had no direct assertion before)."""
    from repro.launch.roofline import collective_bytes

    stats = collective_bytes(FAKE_HLO, default_group=16)
    assert stats.by_kind["all-to-all"] == pytest.approx(
        (15 / 16) * 64 * 512 * 4
    )
    # default_group must not leak into ops that carry explicit groups
    stats2 = collective_bytes(FAKE_HLO, default_group=4)
    assert stats2.by_kind["all-gather"] == stats.by_kind["all-gather"]
    assert stats2.by_kind["all-reduce"] == stats.by_kind["all-reduce"]


def test_group_size_fallbacks():
    from repro.launch.roofline import _group_size

    assert _group_size("replica_groups=[32,16]<=[512]", 8) == 16
    assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}", 8) == 4
    assert _group_size("channel_id=1, dimensions={1}", 8) == 8


def test_roofline_terms_and_bottleneck():
    from repro.launch.roofline import CollectiveStats, roofline_terms

    coll = CollectiveStats()
    coll.add("all-reduce", 50e9)  # exactly 1s of link time
    rl = roofline_terms(dict(flops=197e12 * 0.5, **{"bytes accessed": 819e9 * 0.25}),
                        coll, n_chips=256, model_flops_total=197e12 * 0.5 * 256 * 0.4)
    assert rl.compute_s == pytest.approx(0.5)
    assert rl.memory_s == pytest.approx(0.25)
    assert rl.collective_s == pytest.approx(1.0)
    assert rl.bottleneck == "collective"
    assert rl.useful_ratio == pytest.approx(0.4)


def test_model_flops_counting():
    from repro.launch.roofline import model_flops
    from repro.configs import get_config
    from repro.models.config import SHAPES

    dense = get_config("qwen1.5-4b")
    moe = get_config("deepseek-moe-16b")
    tr = SHAPES["train_4k"]
    assert model_flops(dense, tr) == 6.0 * dense.n_params() * tr.global_batch * tr.seq_len
    # MoE active < total
    assert moe.n_active_params() < moe.n_params()
    assert model_flops(moe, tr) == 6.0 * moe.n_active_params() * tr.global_batch * tr.seq_len


# ------------------------- property tests (hypothesis) ---------------------


if HAVE_HYPOTHESIS:

    level_strategy = st.lists(
        st.tuples(st.integers(0, 400), st.integers(0, 400), st.integers(0, 200)),
        min_size=1, max_size=30,
    ).filter(lambda ls: sum(sum(l) for l in ls) > 0)

    @settings(max_examples=40, deadline=None)
    @given(levels=level_strategy, kb=st.sampled_from([4, 8, 16, 32]),
           disc=st.sampled_from(["levels", "list"]))
    def test_schedule_invariants(levels, kb, disc):
        stats = stats_from_levels(levels)
        c1 = schedule_stats(stats, SramTopology(kb, 1), discipline=disc)
        c3 = schedule_stats(stats, SramTopology(kb, 3), discipline=disc)
        c6 = schedule_stats(stats, SramTopology(kb, 6), discipline=disc)
        # more concurrency never increases cycles
        assert c3.total_cycles <= c1.total_cycles
        assert c6.total_cycles <= c3.total_cycles
        # cycles at least cover the dependency depth
        assert c1.total_cycles >= stats.n_levels
        # op accounting is exact
        for c in (c1, c3, c6):
            assert sum(c.op_counts.values()) == stats.total_gates
            assert c.total_cycles > 0
            assert c.active_macro_cycles >= 0

    @settings(max_examples=30, deadline=None)
    @given(levels=level_strategy)
    def test_wider_macro_never_slower(levels):
        stats = stats_from_levels(levels)
        prev = None
        for kb in (4, 8, 16, 32):
            c = schedule_stats(stats, SramTopology(kb, 1), discipline="list")
            if prev is not None:
                assert c.total_cycles <= prev
            prev = c.total_cycles

else:  # pragma: no cover - CI installs the test extra

    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[test])")
    def test_property_scheduler():
        pass
