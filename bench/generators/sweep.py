"""Closed-loop suite sweeps: one caller, an architect's script, calls
`explore_suite` back to back over frozen characterization data, each
call with freshly drawn Monte-Carlo energy-model variants.

Traffic parameters: ``variants`` (V per call), ``sigma`` (relative
spread of each varied model field).  The front half is bypassed: the
characterization is the configuration's frozen data, so every call is
the fused back half plus its host-side assembly.
"""

from __future__ import annotations

import time

import numpy as np

import common
import program
import reference as ref

#: Widest relative gap allowed between a winner energy the program
#: reports and the reference's best energy for that (circuit, variant).
#: PERF.md gives the chip readings it was set from.
WINNER_ENERGY_REL_ERR_LIMIT = 1e-10
#: Calls compared after the window at most (a seeded sample beyond).
MAX_CHECKED_CALLS = 24


def reference_state(ctx: common.Ctx) -> dict:
    """What the reference and the control need, without the program."""
    return dict(cha_dicts=common.frozen_cha(ctx),
                recipes=ref.recipes(ctx.config["recipes"]),
                topos=ref.topologies(ctx.config["topologies"]))


def setup(ctx: common.Ctx) -> dict:
    state = reference_state(ctx)
    state.update(
        suite=program.suite(common.netlists(ctx)),
        cha=program.cha(state["cha_dicts"]),
        prog_topos=program.topologies(ctx.config["topologies"]),
    )
    # Two warm calls: the first compiles (or loads) the fused program,
    # the second runs every host path once more at steady state.
    for i in range(2):
        call(ctx, state, common.monte_carlo(
            ctx.config, ctx.rng(1, i), ctx.traffic["variants"],
            ctx.traffic["sigma"]))
    return state


def call(ctx: common.Ctx, state: dict, model: dict) -> dict:
    """One `explore_suite` call; returns its per-(circuit, variant)
    winners and winner energies."""
    from repro.core.explorer import explore_suite

    res = explore_suite(
        state["suite"], state["prog_topos"], state["recipes"][1:],
        mode=ctx.config["mode"], discipline=ctx.config["discipline"],
        cha=state["cha"], model_sweep=program.model_table(model),
    )
    if "devices" not in ctx.counters:
        # chips the fused outputs span: 1, or the variant-sharded count
        raw = next(iter(res.values())).variation.grid._raw("energy_nj")
        ctx.counters["devices"] = len(getattr(raw, "sharding").device_set)
    return {
        name: dict(
            winners=[(tuple(r), t.name) for r, t in r_.variation.winners],
            energy=np.asarray(r_.variation.winner_energy_nj, dtype=np.float64),
        )
        for name, r_ in res.items()
    }


def window(ctx: common.Ctx, state: dict) -> common.Window:
    v, sigma = ctx.traffic["variants"], ctx.traffic["sigma"]
    per_call = (len(state["suite"]) * v * len(state["topos"])
                * len(state["recipes"]))
    calls, ends = [], []
    t0 = time.perf_counter_ns()
    deadline = t0 + int(ctx.seconds * 1e9)
    while True:
        i = len(calls)
        model = common.monte_carlo(ctx.config, ctx.rng(2, i), v, sigma)
        with ctx.spans.span("bench.sweep.call"):
            out = call(ctx, state, model)
        calls.append(dict(index=i, answers=out))
        ends.append(time.perf_counter_ns())
        if ends[-1] >= deadline:
            break
    t1 = time.perf_counter_ns()
    call_ms = np.diff([t0] + ends) / 1e6
    ctx.counters["calls"] = len(calls)
    ctx.counters["designs_per_call"] = per_call
    ctx.counters["call_ms"] = {
        q: common.percentile(call_ms, p)
        for q, p in (("min", 0), ("p10", 10), ("p50", 50), ("p90", 90), ("max", 100))}
    # the window's calls in fifths: a drift over the window shows here
    ctx.counters["call_ms"]["fifths"] = [float(np.mean(c))
                                         for c in np.array_split(call_ms, 5)]
    return common.Window(
        start_ns=t0, end_ns=t1, attempted=len(calls), failed=0,
        end_to_end={"sweep_designs_per_s": len(calls) * per_call / ((t1 - t0) / 1e9)},
        state=dict(calls=calls),
    )


# ---------------------------------------------------------------------------
# Correctness: every (circuit, variant) winner of the checked calls
# against the plain reference
# ---------------------------------------------------------------------------


def reference_setup(ctx: common.Ctx, state: dict) -> dict:
    total_bits = np.array([t["total_kb"] * 8192 for t in state["topos"]])
    out = {}
    for name, rows in state["cha_dicts"].items():
        work = ref.workload([rows[r] for r in state["recipes"]])
        sched = ref.schedule(work, state["topos"])
        out[name] = dict(
            work=work, sched=sched,
            feasible=ref.capacity_feasible(total_bits, ref.min_gates(work)),
        )
    return out


def reference_answers(ctx: common.Ctx, state: dict, refs: dict, index: int,
                      xp=np, dtype=np.float64) -> dict:
    """Answers of one call computed by the reference itself, in
    ``dtype`` on ``xp``: the control when that is below float64."""
    model = common.monte_carlo(ctx.config, ctx.rng(2, index),
                               ctx.traffic["variants"], ctx.traffic["sigma"])
    out = {}
    for name, r in refs.items():
        e, t = ref.energy(r["work"], state["topos"], r["sched"], model, xp, dtype)
        e = np.asarray(e, dtype=np.float64)
        idx = ref.select(e, np.asarray(t, dtype=np.float64), r["sched"]["fits"],
                         r["feasible"])
        n_r = e.shape[2]
        out[name] = dict(
            winners=[(state["recipes"][i % n_r], state["topos"][i // n_r]["name"])
                     for i in idx],
            energy=np.array([e[v].flat[i] for v, i in enumerate(idx)]),
        )
    return out


def score(ctx: common.Ctx, state: dict, refs: dict, calls: list[dict]) -> float:
    """Widest relative gap, over every (call, circuit, variant), between
    the reported winner energy, the reference energy of the reported
    winner design, and the reference's best energy."""
    r_of = {r: i for i, r in enumerate(state["recipes"])}
    t_of = {t["name"]: i for i, t in enumerate(state["topos"])}
    worst = 0.0
    for c in calls:
        model = common.monte_carlo(ctx.config, ctx.rng(2, c["index"]),
                                   ctx.traffic["variants"], ctx.traffic["sigma"])
        for name, r in refs.items():
            e, t = ref.energy(r["work"], state["topos"], r["sched"], model)
            idx = ref.select(e, t, r["sched"]["fits"], r["feasible"])
            got = c["answers"].get(name)
            if got is None or len(got["winners"]) != len(idx):
                return float("inf")
            for v, i in enumerate(idx):
                best = float(e[v].flat[i])
                rec, topo = got["winners"][v]
                if rec not in r_of or topo not in t_of:
                    return float("inf")
                design = float(e[v, t_of[topo], r_of[rec]])
                worst = max(worst, common.rel_err(float(got["energy"][v]), best),
                            common.rel_err(design, best))
    return worst


def checked_calls(ctx: common.Ctx, calls: list[dict]) -> list[dict]:
    if len(calls) <= MAX_CHECKED_CALLS:
        return calls
    rng = ctx.rng(3)
    pick = rng.choice(np.arange(1, len(calls) - 1), MAX_CHECKED_CALLS - 2,
                      replace=False)
    return [calls[0]] + [calls[i] for i in sorted(pick)] + [calls[-1]]


def check(ctx: common.Ctx, state: dict, win: common.Window) -> list[common.Check]:
    refs = reference_setup(ctx, state)
    calls = checked_calls(ctx, win.state["calls"])
    return [common.Check("winner_energy_rel_err", score(ctx, state, refs, calls),
                         WINNER_ENERGY_REL_ERR_LIMIT)]
