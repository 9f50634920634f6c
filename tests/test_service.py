"""ExplorationService: parity, cache fast paths, failure injection.

The service's contract is that a warm request is *bit-identical* to a
fresh offline `explore_request` call — same winner cell, same tiering
and tie-breaking, same variation summary — while skipping every
expensive stage it can (characterization via the fingerprint memo, the
device sweep via the grid cache, jit compilation via shape bucketing).
These tests pin each of those properties separately, then the failure
injection ones pin the other half of the contract: bad requests get
structured errors, good batch-mates are unaffected, and the worker
survives everything.

Uses `pump()` (passive, single-threaded) mode so cache and trace
assertions are deterministic; the stress test at the bottom exercises
the real worker thread.
"""

import dataclasses
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import batch as B
from repro.core.aig import Aig
from repro.core.batch import (
    LEVEL_PAD,
    PAD_CIRCUIT_PREFIX,
    SuiteTable,
    bucket_levels,
    bucket_suite,
    ceil_pow2,
    pad_suite,
    trace_counts,
)
from repro.core.circuits import gen_adder, gen_max
from repro.core.explorer import explore_request
from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable
from repro.core.transforms import characterize_suite
from repro.serve import explore_service as ES
from repro.serve.explore_service import (
    ExplorationService,
    ExploreRequest,
)

TOPOS = TOPOLOGY_LIBRARY[:5]
RECIPES = [(), ("Rw",), ("Ba", "Rw"), ("Rf",)]


@pytest.fixture(scope="module")
def adder():
    return gen_adder(6)


@pytest.fixture(scope="module")
def maxc():
    return gen_max(6, 2)


@pytest.fixture(scope="module")
def svc():
    s = ExplorationService(sram_list=TOPOS, recipes=RECIPES, start=False)
    yield s
    s.close()


def nan_table() -> ModelTable:
    """A model sweep whose every variant yields non-finite energies."""
    t = ModelTable.monte_carlo(n=3, seed=0)
    return dataclasses.replace(
        t,
        e_op_fj=np.full_like(t.e_op_fj, np.nan),
        e_op_marginal_fj=np.full_like(t.e_op_marginal_fj, np.nan),
        e_macro_cycle_fj=np.full_like(t.e_macro_cycle_fj, np.nan),
        e_col_cycle_fj=np.full_like(t.e_col_cycle_fj, np.nan),
        writeback_fj_nonresonant=np.full_like(
            t.writeback_fj_nonresonant, np.nan
        ),
    )


# ------------------------- bucket-shape helpers ----------------------------


def test_ceil_pow2():
    assert [ceil_pow2(n) for n in (0, 1, 2, 3, 4, 5, 8, 9)] == [
        1, 1, 2, 4, 4, 8, 8, 16,
    ]


def test_bucket_levels():
    assert bucket_levels(1) == LEVEL_PAD
    assert bucket_levels(LEVEL_PAD) == LEVEL_PAD
    assert bucket_levels(LEVEL_PAD + 1) == 2 * LEVEL_PAD
    assert bucket_levels(3 * LEVEL_PAD) == 4 * LEVEL_PAD


def test_pad_suite_shapes(adder, maxc):
    cha = characterize_suite(
        {"a": adder, "m": maxc, "a5": gen_adder(5)}, RECIPES
    )
    suite = SuiteTable.from_cha(cha)
    padded, bucket = bucket_suite(suite, len(TOPOS), 1)
    c, r, l, _ = padded.ops.shape
    assert c == ceil_pow2(len(suite.circuits)) == 4  # 3 circuits -> 4
    assert l == bucket_levels(suite.ops.shape[2])
    assert bucket == (c, r, l, len(TOPOS), 1)
    # padding rows are copies of circuit 0 (finite workloads), real rows
    # are untouched
    assert padded.circuits[:3] == suite.circuits
    assert all(n.startswith(PAD_CIRCUIT_PREFIX)
               for n in padded.circuits[3:])
    np.testing.assert_array_equal(
        padded.ops[:3, :, : suite.ops.shape[2]], suite.ops
    )
    np.testing.assert_array_equal(
        padded.ops[3], padded.ops[0]
    )
    # no-op padding returns the same object
    assert pad_suite(padded) is padded
    with pytest.raises(ValueError):
        pad_suite(suite, n_circuits=1)


# ------------------------------- parity ------------------------------------


def offline_winner_cell(off):
    """The offline winner's *device-grid* cell: the service's metrics come
    from the same fused kernel, so equality here is bit-exact (the scalar
    `best.metrics` recompute can differ by 1 ulp)."""
    ti = off.grid.topologies.index(off.best.topo)
    ri = off.grid.recipes.index(tuple(off.best.recipe))
    return off.grid.cell(ti, ri)


def test_winner_parity_plain(svc, adder):
    resp = svc.explore(adder)
    assert resp.ok, resp.error
    off = explore_request(adder, TOPOS, RECIPES)
    assert resp.winner.topology.name == off.best.topo.name
    assert resp.winner.recipe == tuple(off.best.recipe)
    cell = offline_winner_cell(off)
    assert resp.winner.energy_nj == cell.energy_nj
    assert resp.winner.latency_ns == cell.latency_ns
    assert resp.winner.power_mw == cell.power_mw
    assert resp.winner.energy_nj == pytest.approx(
        off.best.metrics.energy_nj, rel=1e-9
    )
    assert resp.winner.inductor_nh == off.inductor_nh
    assert resp.fingerprint == adder.fingerprint()
    assert resp.bucket is not None


def test_winner_parity_budget_and_latency(svc, adder):
    kb = sorted(t.total_kb for t in TOPOS)[1]  # excludes some topologies
    resp = svc.explore(adder, max_memory_kb=kb, max_latency_ns=200.0)
    assert resp.ok, resp.error
    off = explore_request(
        adder, TOPOS, RECIPES, max_memory_kb=kb, max_latency_ns=200.0
    )
    assert resp.winner.topology.name == off.best.topo.name
    assert resp.winner.recipe == tuple(off.best.recipe)
    assert resp.winner.energy_nj == offline_winner_cell(off).energy_nj
    assert resp.winner.topology.total_kb <= kb


def test_variation_parity(svc, maxc):
    table = ModelTable.monte_carlo(n=4, seed=2)
    resp = svc.explore(
        ExploreRequest(maxc, model_sweep=table, max_latency_ns=500.0)
    )
    assert resp.ok, resp.error
    off = explore_request(
        maxc, TOPOS, RECIPES, model_sweep=table, max_latency_ns=500.0
    )
    v, vo = resp.variation, off.variation
    assert [t.name for _, t in v.winners] == [t.name for _, t in vo.winners]
    assert [r for r, _ in v.winners] == [tuple(r) for r, _ in vo.winners]
    assert v.winner_share == vo.winner_share
    assert v.best_yield == vo.best_yield
    assert v.latency_yield == vo.latency_yield
    np.testing.assert_array_equal(v.winner_energy_nj, vo.winner_energy_nj)
    assert v.energy_quantiles == vo.energy_quantiles
    assert v.cvar() == vo.cvar()


# --------------------------- cache fast paths ------------------------------


def test_cha_cache_hit_skips_front_half(adder, maxc, monkeypatch):
    calls = []
    real = ES.characterize_suite

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(ES, "characterize_suite", counting)
    s = ExplorationService(sram_list=TOPOS, recipes=RECIPES, start=False)
    r1 = s.explore(adder)
    assert r1.ok and not r1.cha_cache_hit
    n_after_first = len(calls)
    assert n_after_first >= 1
    # same fingerprint, different constraints: front half never re-runs
    r2 = s.explore(adder, max_latency_ns=1e6)
    r3 = s.explore(adder, max_memory_kb=1e9)
    assert r2.ok and r2.cha_cache_hit
    assert r3.ok and r3.cha_cache_hit
    assert len(calls) == n_after_first
    # a new fingerprint does re-characterize
    r4 = s.explore(maxc)
    assert r4.ok and not r4.cha_cache_hit
    assert len(calls) == n_after_first + 1
    s.close()


def test_constraint_change_is_rerank_only(svc, adder):
    base = svc.explore(adder)
    assert base.ok
    before = trace_counts()
    for kw in (
        dict(max_latency_ns=1e6),
        dict(max_latency_ns=25.0),
        dict(max_memory_kb=max(t.total_kb for t in TOPOS)),
        dict(max_memory_kb=sorted(t.total_kb for t in TOPOS)[1],
             max_latency_ns=1e3),
    ):
        r = svc.explore(adder, **kw)
        assert r.ok, r.error
        assert r.cha_cache_hit and r.grid_cache_hit
    # pure masked-argmin re-ranks: zero new jit traces of any kernel
    assert trace_counts() == before


def test_same_bucket_reuses_trace(svc, adder, maxc):
    # both tiny circuits land in the same (C, R, L, T, V) bucket; after
    # each has been evaluated once, re-evaluating ANY same-shape suite
    # costs zero new traces
    assert svc.explore(adder).ok
    assert svc.explore(maxc).ok
    before = trace_counts()
    evaluate_calls = svc.stats()["evaluate_calls"]
    fresh = gen_adder(5)  # new fingerprint, same bucket
    r = svc.explore(fresh)
    assert r.ok and not r.grid_cache_hit
    assert svc.stats()["evaluate_calls"] == evaluate_calls + 1
    assert trace_counts() == before  # compiled sweep reused


# --------------------------- failure injection -----------------------------


def test_malformed_circuit(svc):
    r = svc.explore(ExploreRequest(circuit="not an aig"))
    assert not r.ok and r.error.code == "malformed-circuit"
    no_po = Aig(4, name="no-outputs")
    r2 = svc.explore(ExploreRequest(circuit=no_po))
    assert not r2.ok and r2.error.code == "malformed-circuit"
    r3 = svc.explore(ExploreRequest(circuit=gen_adder(4), model_sweep="x"))
    assert not r3.ok and r3.error.code == "malformed-circuit"


def test_infeasible_memory_budget(svc, adder):
    r = svc.explore(adder, max_memory_kb=0.001)
    assert not r.ok and r.error.code == "infeasible-memory"
    assert "smallest candidate" in r.error.message
    # the offline path rejects the same budget
    with pytest.raises(ValueError):
        explore_request(adder, TOPOS, RECIPES, max_memory_kb=0.001)


def test_nan_sweep_structured_error(svc, adder):
    r = svc.explore(ExploreRequest(adder, model_sweep=nan_table()))
    assert not r.ok and r.error.code == "no-finite-energy"


def test_bad_batch_mates_do_not_sink_healthy(adder, maxc):
    """One pump batch with every failure mode + two healthy requests:
    the healthy ones complete with correct winners."""
    s = ExplorationService(
        sram_list=TOPOS, recipes=RECIPES, start=False, max_batch=8
    )
    futs = s.submit_batch([
        ExploreRequest(adder),
        ExploreRequest(circuit=12345),
        ExploreRequest(adder, max_memory_kb=0.001),
        ExploreRequest(maxc, model_sweep=nan_table()),
        ExploreRequest(maxc, max_latency_ns=1e6),
    ])
    assert s.pump() == 5
    rs = [f.result(timeout=0) for f in futs]
    assert rs[0].ok
    assert rs[1].error.code == "malformed-circuit"
    assert rs[2].error.code == "infeasible-memory"
    assert rs[3].error.code == "no-finite-energy"
    assert rs[4].ok
    off = explore_request(adder, TOPOS, RECIPES)
    assert rs[0].winner.energy_nj == offline_winner_cell(off).energy_nj
    offm = explore_request(maxc, TOPOS, RECIPES, max_latency_ns=1e6)
    assert rs[4].winner.energy_nj == offline_winner_cell(offm).energy_nj
    s.close()


def test_submit_after_close_raises(adder):
    s = ExplorationService(sram_list=TOPOS, recipes=RECIPES, start=False)
    s.close()
    with pytest.raises(RuntimeError):
        s.submit(ExploreRequest(adder))


def test_close_fails_queued_requests(adder):
    s = ExplorationService(sram_list=TOPOS, recipes=RECIPES, start=False)
    fut = s.submit(ExploreRequest(adder))
    s.close()  # passive mode: queued request resolves with shutdown error
    r = fut.result(timeout=0)
    assert not r.ok and r.error.code == "shutdown"


# ------------------------- threaded stress test ----------------------------


def test_threaded_submit_cancel_stress(adder, maxc):
    """Multiple submitter threads race the worker with mixed good/bad
    requests and eager cancellations: every future terminates, every
    non-cancelled response is structured, all winners agree with the
    offline reference."""
    s = ExplorationService(
        sram_list=TOPOS, recipes=RECIPES, start=True, max_batch=4
    )
    off_a = offline_winner_cell(explore_request(adder, TOPOS, RECIPES))
    reqs = [
        ExploreRequest(adder),
        ExploreRequest(maxc),
        ExploreRequest(adder, max_latency_ns=1e6),
        ExploreRequest(adder, max_memory_kb=0.001),
        ExploreRequest(circuit=None),
    ]
    futures, lock = [], threading.Lock()

    def submitter(k: int):
        for i in range(6):
            f = s.submit(reqs[(k + i) % len(reqs)])
            if (k + i) % 5 == 4:
                f.cancel()  # may or may not win the race — both fine
            with lock:
                futures.append(f)

    threads = [threading.Thread(target=submitter, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(futures) == 18
    done = 0
    for f in futures:
        if f.cancelled():
            continue
        r = f.result(timeout=120)
        done += 1
        if r.ok:
            assert r.winner is not None
            if r.request.circuit is adder and r.request.max_memory_kb is None:
                assert r.winner.energy_nj == off_a.energy_nj
        else:
            assert r.error.code in {
                "malformed-circuit", "infeasible-memory", "shutdown"
            }
    assert done >= 1
    st = s.stats()
    assert st["submitted"] == 18
    assert st["served"] + st["errors"] + st["cancelled"] == 18
    s.close()
    # close is idempotent and the service refuses new work afterwards
    s.close()
    with pytest.raises(RuntimeError):
        s.submit(ExploreRequest(adder))


def test_serve_explore_cli_exit_code():
    """The explore CLI exits non-zero when a request fails (here: a memory
    budget no topology fits) and 0 when every request is served."""
    from repro.launch.serve import main

    base = ["explore", "--circuits", "adder", "--recipes", "2",
            "--requests", "2"]
    assert main(base) == 0
    assert main(base + ["--max-memory-kb", "0.001"]) == 1
