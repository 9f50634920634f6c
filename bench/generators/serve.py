"""Open-loop queries to a warm `ExplorationService`: independent designers
asking which implementation of a circuit is cheapest under their memory
budget and latency bound.

Arrivals are a Poisson process at the mix's fixed ``rate_per_s``: the
run's ``round(rate * seconds)`` arrival times are uniform over the
window, which is a Poisson process conditioned on its count, so every
seed offers the same load in another order.  Circuit popularity is Zipf
(``zipf_s``) over the suite, its rank order drawn from the seed; a share
``nominal_share`` of requests use the nominal energy model and the rest
one of ``n_tables`` Monte-Carlo tables (``table_variants`` variants,
``sigma``) made from the seed; constraints are none, latency, memory or
both, equally often, drawn from the nominal grids so that every request
has an admissible design.  Each request is timed from when it was due;
one that fails or is never answered counts as over any limit.

Set-up fills the service's characterization cache from the frozen data
and warms every (circuit, model table) grid, so no fused pass and no
characterization happens in the window: the service's queue, batching
and re-rank do the work.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

import common
import program
import reference as ref

#: See the sweep generator: the same comparison, per answered request and
#: per variant of a Monte-Carlo request.
WINNER_ENERGY_REL_ERR_LIMIT = 1e-10
#: Requests answered with an error, or never answered: exact, limit 0.
WRONG_ANSWERS_LIMIT = 0
#: Seconds past the window's close that the run waits for answers.
GRACE_S = 60.0
KINDS = ("none", "latency", "memory", "both")


def _reference(ctx: common.Ctx, state: dict) -> dict:
    """Per circuit: the reference's workload, schedule and capacity
    threshold (model-free)."""
    out = {}
    for name, rows in state["cha"].items():
        work = ref.workload([rows[r] for r in state["recipes"]])
        out[name] = dict(work=work, sched=ref.schedule(work, state["topos"]),
                         min_gates=ref.min_gates(work))
    return out


def _models(ctx: common.Ctx) -> list[dict]:
    """Model specs: index 0 the nominal model, then the Monte-Carlo
    tables, each as reference arrays."""
    t = ctx.traffic
    return [common.nominal_model(ctx.config)] + [
        common.monte_carlo(ctx.config, ctx.rng(5, k), t["table_variants"], t["sigma"])
        for k in range(t["n_tables"])
    ]


def schedule(ctx: common.Ctx, state: dict) -> list[dict]:
    """The run's requests, from the seed alone: due time, circuit, model
    index (0 nominal) and constraints, each admissible under the nominal
    model."""
    t = ctx.traffic
    rng = ctx.rng(6)
    n = int(round(t["rate_per_s"] * ctx.seconds))
    due = np.sort(rng.uniform(0.0, ctx.seconds, n))
    names = list(state["cha"])
    order = [names[i] for i in rng.permutation(len(names))]
    p = 1.0 / np.arange(1, len(names) + 1) ** t["zipf_s"]
    p /= p.sum()
    bits = np.array([tp["total_kb"] * 8192 for tp in state["topos"]])
    kbs = np.array([tp["total_kb"] for tp in state["topos"]], dtype=float)
    out = []
    for i in range(n):
        name = order[int(rng.choice(len(names), p=p))]
        model = 0 if rng.random() < t["nominal_share"] else 1 + int(rng.integers(t["n_tables"]))
        kind = KINDS[int(rng.integers(len(KINDS)))]
        r = state["refs"][name]
        e, lat = state["nominal"][name]
        mem = lat_bound = None
        within = None
        if kind in ("memory", "both"):
            ok_b = []
            for b in sorted(set(kbs)):
                w = kbs <= b
                feas = ref.capacity_feasible(bits, r["min_gates"], w)
                if (r["sched"]["fits"] & (feas & w)[:, None]).any():
                    ok_b.append(b)
            mem = float(ok_b[int(rng.integers(len(ok_b)))])
            within = kbs <= mem
        if kind in ("latency", "both"):
            feas = ref.capacity_feasible(bits, r["min_gates"], within)
            adm = r["sched"]["fits"] & feas[:, None]
            if within is not None:
                adm &= within[:, None]
            ls = lat[0][adm]
            lat_bound = float(rng.uniform(ls.min(), ls.max()))
        out.append(dict(due_s=float(due[i]), circuit=name, model=model,
                        max_memory_kb=mem, max_latency_ns=lat_bound))
    return out


def setup(ctx: common.Ctx) -> dict:
    from repro.core.transforms import CharacterizationCache
    from repro.serve.explore_service import ExplorationService, ExploreRequest

    state = reference_state(ctx)
    suite = state["suite"] = program.suite(common.netlists(ctx))
    cache_dir = ctx.work_dir / "svc-cache"
    shutil.rmtree(cache_dir, ignore_errors=True)
    cache = CharacterizationCache(cache_dir)
    for name, aig in suite.items():
        cache.store(aig.fingerprint(), program.cha({name: state["cha"][name]})[name])
    state["tables"] = [None] + [program.model_table(m) for m in state["models"][1:]]
    svc = ExplorationService(
        sram_list=program.topologies(ctx.config["topologies"]),
        recipes=state["recipes"][1:], model=program.energy_model(ctx.config),
        mode=ctx.config["mode"], discipline=ctx.config["discipline"],
        cache=cache, cha_backend="device", max_batch=ctx.traffic["max_batch"],
    )
    state["svc"] = svc
    # Warm every (circuit, model table) grid one request at a time, so
    # each fused pass has the one-circuit bucket shape, then every
    # re-rank path (each constraint kind, nominal and Monte-Carlo; a
    # budget that excludes some topologies, so the masked path runs).
    warm = [ExploreRequest(circuit=aig, model_sweep=tab)
            for aig in suite.values() for tab in state["tables"]]
    first = next(iter(state["refs"]))
    bound = float(np.max(state["nominal"][first][1]))
    budget = float(max(t["total_kb"] for t in state["topos"]) - 1)
    for tab in state["tables"][:2]:
        for mem, lat in ((None, bound), (budget, None), (budget, bound)):
            warm.append(ExploreRequest(circuit=suite[first], model_sweep=tab,
                                       max_memory_kb=mem, max_latency_ns=lat))
    for req in warm:
        resp = svc.submit(req).result()
        if not resp.ok:
            raise RuntimeError(f"warm-up request failed: {resp.error}")
    return state


def window(ctx: common.Ctx, state: dict) -> common.Window:
    """Offer the schedule, wait for every answer (at most `GRACE_S` past
    the close), then shut the service down."""
    win = offer(ctx, state)
    state.pop("svc").close()
    return win


def offer(ctx: common.Ctx, state: dict) -> common.Window:
    """Offer the run's schedule to the warm service and collect every
    answer; the service stays up."""
    from repro.serve.explore_service import ExploreRequest

    svc = state["svc"]
    sched = state["schedule"]
    done = [None] * len(sched)
    sent = [0.0] * len(sched)
    futs = []

    def on_done(i):
        def cb(_f):
            done[i] = time.perf_counter()
        return cb

    t0 = time.perf_counter()
    for i, q in enumerate(sched):
        wait = t0 + q["due_s"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        fut = svc.submit(ExploreRequest(
            circuit=state["suite"][q["circuit"]],
            model_sweep=state["tables"][q["model"]],
            max_memory_kb=q["max_memory_kb"], max_latency_ns=q["max_latency_ns"],
            tag=str(i)))
        fut.add_done_callback(on_done(i))
        futs.append(fut)
    t_close = t0 + ctx.seconds
    resps = []
    for fut in futs:
        try:
            resps.append(fut.result(timeout=max(0.0, t_close + GRACE_S - time.perf_counter())))
        except TimeoutError:
            resps.append(None)
    t1 = time.perf_counter()
    cap = t_close + GRACE_S
    lat_ms, failed = [], 0
    for i, q in enumerate(sched):
        due = t0 + q["due_s"]
        ok = resps[i] is not None and resps[i].ok and done[i] is not None
        failed += not ok
        lat_ms.append(((done[i] if ok else max(cap, t1)) - due) * 1e3)
    answered = [r for r in resps if r is not None and r.ok]
    ctx.counters.update(
        requests=len(sched),
        queued_ms=[r.queued_ms for r in answered],
        service_ms=[r.service_ms for r in answered],
        gen_late_ms=[(sent[i] - (t0 + q["due_s"])) * 1e3 for i, q in enumerate(sched)],
    )
    return common.Window(
        start_ns=int(t0 * 1e9), end_ns=int(max(t_close, t1) * 1e9),
        attempted=len(sched), failed=failed,
        end_to_end={"query_p95_ms": common.percentile(lat_ms, 95)},
        state=dict(resps=resps, latency_ms=lat_ms),
    )


# ---------------------------------------------------------------------------
# Correctness: every answered request against the plain reference
# ---------------------------------------------------------------------------


def score(ctx: common.Ctx, state: dict, answers: list) -> tuple[float, int]:
    """(widest relative winner-energy gap over every answered request and
    variant, requests answered with an error or never answered).

    ``answers[i]``: None, or ``(winners, energies)`` with one
    ``(recipe, topology name)`` and one reported energy per variant."""
    bits = np.array([t["total_kb"] * 8192 for t in state["topos"]])
    kbs = np.array([t["total_kb"] for t in state["topos"]], dtype=float)
    r_of = {r: i for i, r in enumerate(state["recipes"])}
    t_of = {t["name"]: i for i, t in enumerate(state["topos"])}
    grids: dict = {}
    worst, wrong = 0.0, 0
    for q, ans in zip(state["schedule"], answers):
        if ans is None:
            wrong += 1
            continue
        r = state["refs"][q["circuit"]]
        key = (q["circuit"], q["model"])
        if key not in grids:
            grids[key] = ref.energy(r["work"], state["topos"], r["sched"],
                                    state["models"][q["model"]])
        e, lat = grids[key]
        within = None if q["max_memory_kb"] is None else kbs <= q["max_memory_kb"]
        feas = ref.capacity_feasible(bits, r["min_gates"], within)
        idx = ref.select(e, lat, r["sched"]["fits"], feas, q["max_latency_ns"], within)
        winners, energies = ans
        if len(winners) != len(idx):
            wrong += 1
            continue
        for v, i in enumerate(idx):
            rec, topo = winners[v]
            if rec not in r_of or topo not in t_of:
                worst = float("inf")
                continue
            best = float(e[v].flat[i])
            worst = max(worst, common.rel_err(float(energies[v]), best),
                        common.rel_err(float(e[v, t_of[topo], r_of[rec]]), best))
    return worst, wrong


def reference_answers(ctx: common.Ctx, state: dict, xp=np, dtype=np.float64) -> list:
    """Answers of the run's schedule computed by the reference itself in
    ``dtype`` on ``xp``: the control when that is below float64."""
    bits = np.array([t["total_kb"] * 8192 for t in state["topos"]])
    kbs = np.array([t["total_kb"] for t in state["topos"]], dtype=float)
    grids: dict = {}
    out = []
    for q in state["schedule"]:
        r = state["refs"][q["circuit"]]
        key = (q["circuit"], q["model"])
        if key not in grids:
            e, lat = ref.energy(r["work"], state["topos"], r["sched"],
                                state["models"][q["model"]], xp, dtype)
            grids[key] = (np.asarray(e, dtype=np.float64), np.asarray(lat, dtype=np.float64))
        e, lat = grids[key]
        within = None if q["max_memory_kb"] is None else kbs <= q["max_memory_kb"]
        feas = ref.capacity_feasible(bits, r["min_gates"], within)
        idx = ref.select(e, lat, r["sched"]["fits"], feas, q["max_latency_ns"], within)
        n_r = e.shape[2]
        out.append(([(state["recipes"][i % n_r], state["topos"][i // n_r]["name"])
                     for i in idx], [float(e[v].flat[i]) for v, i in enumerate(idx)]))
    return out


def reference_state(ctx: common.Ctx) -> dict:
    """What the reference, the schedule and the control need, without the
    program."""
    state = dict(cha=common.frozen_cha(ctx), recipes=ref.recipes(ctx.config["recipes"]),
                 topos=ref.topologies(ctx.config["topologies"]))
    state["refs"] = _reference(ctx, state)
    state["models"] = _models(ctx)
    state["nominal"] = {
        name: ref.energy(r["work"], state["topos"], r["sched"], state["models"][0])
        for name, r in state["refs"].items()
    }
    state["schedule"] = schedule(ctx, state)
    return state


def answers_of(resps: list) -> list:
    out = []
    for resp in resps:
        if resp is None or not resp.ok:
            out.append(None)
        elif resp.variation is not None:
            out.append(([(tuple(rc), t.name) for rc, t in resp.variation.winners],
                        [float(x) for x in resp.variation.winner_energy_nj]))
        else:
            out.append(([(tuple(resp.winner.recipe), resp.winner.topology.name)],
                        [resp.winner.energy_nj]))
    return out


def check(ctx: common.Ctx, state: dict, win: common.Window) -> list[common.Check]:
    worst, wrong = score(ctx, state, answers_of(win.state["resps"]))
    return [
        common.Check("winner_energy_rel_err", worst, WINNER_ENERGY_REL_ERR_LIMIT),
        common.Check("wrong_answers", wrong, WRONG_ANSWERS_LIMIT),
    ]
