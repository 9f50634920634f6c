"""The service mix's load generator: the seed alone fixes the schedule,
and every request it draws has an admissible design."""

from __future__ import annotations

import numpy as np
import pytest

import reference as ref
from conftest import SERVICE, make_layout, run_mix


@pytest.fixture(scope="module")
def full(tmp_path_factory):
    """The layout at the configuration's own sizes."""
    return make_layout(tmp_path_factory.mktemp("full"), small=None)


@pytest.fixture
def state(full):
    def make(seed: int, seconds: float = 30.0):
        import run

        _spec, ctx, generator = run.prepare(full, SERVICE, seed, seconds, False)
        return ctx, generator, generator.reference_state(ctx)
    return make


def test_same_seed_same_schedule_other_seed_other_order(state):
    _, _, a = state(2**31 + 5)
    _, _, b = state(2**31 + 5)
    _, _, c = state(2**31 + 6)
    assert a["schedule"] == b["schedule"]
    assert a["schedule"] != c["schedule"]
    # the same load, in another order: as many arrivals in the window
    assert len(a["schedule"]) == len(c["schedule"])


def test_schedule_follows_the_mix(state):
    ctx, _, st = state(2**31 + 7)
    t = ctx.traffic
    sched = st["schedule"]
    assert len(sched) == round(t["rate_per_s"] * ctx.seconds)
    due = [q["due_s"] for q in sched]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < ctx.seconds
    nominal = np.mean([q["model"] == 0 for q in sched])
    assert abs(nominal - t["nominal_share"]) < 0.06
    counts = np.bincount([list(st["cha"]).index(q["circuit"]) for q in sched],
                         minlength=len(st["cha"]))
    assert counts.max() > 3 * np.median(counts)  # Zipf: a few circuits dominate


def test_no_request_is_infeasible(state):
    """Every request has a design that fits, is capacity-feasible within
    its budget and meets its latency bound under the nominal model."""
    _, _, st = state(2**31 + 8)
    bits = np.array([t["total_kb"] * 8192 for t in st["topos"]])
    kbs = np.array([t["total_kb"] for t in st["topos"]], dtype=float)
    for q in st["schedule"]:
        r = st["refs"][q["circuit"]]
        _e, lat = st["nominal"][q["circuit"]]
        within = None if q["max_memory_kb"] is None else kbs <= q["max_memory_kb"]
        feas = ref.capacity_feasible(bits, r["min_gates"], within)
        adm = r["sched"]["fits"] & feas[:, None]
        if within is not None:
            adm &= within[:, None]
        if q["max_latency_ns"] is not None:
            adm &= lat[0] <= q["max_latency_ns"]
        assert adm.any(), q


def test_service_mix_runs_small_and_is_correct(layout):
    checks = run_mix(layout, SERVICE)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
