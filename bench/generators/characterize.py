"""Cold characterization: the front half of Algorithm I from an empty
cache, a new netlist's time to its characterization.

The suite's recipes are characterized depth by depth: for d = 1 ..
``max_depth``, `characterize_suite` on each circuit with every recipe of
length at most d, on the device backend, one process, into a cache that
starts empty in every run.  Each call warm-starts from the applications
the calls before it persisted (the cache's prefix warm start), so the
window reaches every circuit's top-level Ba/Rf/Rw/Rs applications first,
sine's resub included, instead of walking the suite circuit by circuit.

The suite is public and fixed, so the seed does not change the work: it
draws only the simulation patterns of the check.  Traffic parameters:
``max_depth``, ``check_words`` (64-bit pattern words per input in the
equivalence check).

The check holds every application the window persisted to three
numbers: its output is the one the benchmark's reference transforms give
for the same source and transform (``expected_outputs``, frozen by
``bench/freeze.py``), it computes its source's function, and its
recorded statistics are its output's.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import time

import numpy as np

import common
import reference as ref

#: Applications whose output is not the reference transforms' output for
#: the same source, applications whose output differs in function from
#: their input, and applications whose recorded statistics differ from the
#: mapping of their output: exact counts, limit 0.
WRONG_OUTPUT_LIMIT = 0
INEQUIVALENT_LIMIT = 0
STATS_MISMATCH_LIMIT = 0
#: Cut widths that put a truth-table query in each word tier of the
#: cone-simulation kernels (<= 5, <= 10, <= 14 variables), and the root
#: counts the transforms ask for (rewrite/refactor 1, resub 2).
WARM_SUPPORTS = (4, 9, 13)
WARM_ROOTS = (1, 2)
#: Roots tried, from the last AND node down, for a cut in each tier.
WARM_ROOT_TRIES = 256
#: Words per input of the signature patterns resub draws.
SIG_WORDS = 32


def fingerprint(d: dict) -> str:
    """sha256 over the int64 bytes of [n_pis], f0, f1, pos: the AIG's
    exact structure, as the cache keys it."""
    h = hashlib.sha256()
    for part in ([d["n_pis"]], d["f0"], d["f1"], d["pos"]):
        h.update(np.asarray(part, dtype=np.int64).tobytes())
    return h.hexdigest()


def recipes_to_depth(ctx: common.Ctx, depth: int) -> list[tuple[str, ...]]:
    return [r for r in ref.recipes(ctx.config["recipes"]) if 1 <= len(r) <= depth]


def _cut(d: dict, root: int, k: int) -> list[int]:
    """A cut of ``root``'s cone with about ``k`` leaves: expand the
    deepest AND leaf into its fanins until the cut is that wide."""
    n_pis, f0, f1 = d["n_pis"], d["f0"], d["f1"]
    cut = {root}
    while len(cut) < k:
        ands = [n for n in cut if n > n_pis]
        if not ands:
            break
        n = max(ands)
        cut.discard(n)
        cut.update((f0[n] >> 1, f1[n] >> 1))
    cut.discard(0)
    return sorted(cut)


def _warm(graphs: list[dict]) -> None:
    """Compile (or load from the compile cache) every cone-simulation
    program the window meets: for each graph it transforms, a query in
    every word tier with each root count at the graph's size bucket, and
    the signature program of the graph's wave shape.  Each program is
    called once, from the first graph that needs it."""
    from repro.core.aig import Aig
    from repro.kernels import aig_sim

    seen = set()
    for d in graphs:
        aig = Aig.from_dict(d)
        prog = aig_sim.compile_aig(aig)
        for k in WARM_SUPPORTS:
            w = aig_sim._tier_for(k)[1]
            want = [n for n in WARM_ROOTS if (prog.n_pad, w, n) not in seen]
            found = _cut_in_tier(d, k) if want else None
            if found is None:
                continue
            root, cut = found
            second = max(n for n in cut if n != root) if len(cut) > 1 else root
            for n_roots in want:
                seen.add((prog.n_pad, w, n_roots))
                roots = (root << 1, second << 1)[:n_roots]
                aig_sim.eval_tts(aig, [(roots, cut)], program=prog)
        if (prog.waves.shape, prog.n_pad) not in seen:
            seen.add((prog.waves.shape, prog.n_pad))
            patterns = np.zeros((aig.n_pis, SIG_WORDS), dtype=np.uint64)
            aig_sim.node_signatures(aig, patterns, program=prog)


def _cut_in_tier(d: dict, k: int) -> tuple[int, list[int]] | None:
    """A root and a cut of its cone in the word tier of ``k`` leaves,
    from the last AND nodes down: a cone too small for the tier at one
    root has room at another."""
    from repro.kernels import aig_sim

    tier = aig_sim._tier_for(k)
    for root in range(len(d["f0"]) - 1, d["n_pis"], -1)[:WARM_ROOT_TRIES]:
        cut = _cut(d, root, k)
        if aig_sim._tier_for(len(cut)) == tier:
            return root, cut
    return None


def setup(ctx: common.Ctx) -> dict:
    from repro.core.aig import Aig

    nets = common.netlists(ctx)
    suite = {n: Aig.from_dict(d) for n, d in nets.items()}
    cache_dir = ctx.work_dir / "cha-cache"
    shutil.rmtree(cache_dir, ignore_errors=True)
    depth1 = common.load_json(ctx.root / ctx.config["warm_graphs"])["circuits"]
    _warm(list(nets.values()) + [g for n in nets for g in depth1[n].values()])
    return dict(nets=nets, suite=suite, cache_dir=cache_dir,
                base_fp={n: fingerprint(d) for n, d in nets.items()})


class WindowClosed(Exception):
    """Raised from the harness's cache once the window has closed, to end
    the characterization call in progress."""


def _timed_cache(root, deadline_ns: int):
    """A `CharacterizationCache` that notes when each application is
    persisted and closes the window at the first one persisted at or
    after ``deadline_ns``: the window then ends on a unit of work, as
    the sweep's does on a call."""
    from repro.core.transforms import CharacterizationCache

    class TimedCache(CharacterizationCache):
        def __init__(self, path):
            super().__init__(path)
            self.persisted: list[tuple[int, str, str, str]] = []

        def store_application(self, circuit_fp, src_fp, transform, out, stats=None):
            super().store_application(circuit_fp, src_fp, transform, out, stats)
            now = time.perf_counter_ns()
            self.persisted.append((now, circuit_fp, src_fp, transform))
            if now >= deadline_ns:
                raise WindowClosed

    return TimedCache(root)


def window(ctx: common.Ctx, state: dict) -> common.Window:
    from repro.core.transforms import characterize_suite

    t0 = time.perf_counter_ns()
    cache = _timed_cache(state["cache_dir"], t0 + int(ctx.seconds * 1e9))
    try:
        for d in range(1, ctx.traffic["max_depth"] + 1):
            recipes = recipes_to_depth(ctx, d)
            for name, aig in state["suite"].items():
                with ctx.spans.span("bench.cha.call"):
                    characterize_suite({name: aig}, recipes, cache=cache,
                                       n_jobs=1, backend="device")
    except Exception as e:  # noqa: BLE001 — the program wraps what the cache raised
        if not isinstance(e, WindowClosed) and not isinstance(e.__cause__, WindowClosed):
            raise
    t1 = cache.persisted[-1][0] if cache.persisted else time.perf_counter_ns()
    # What the window finished: every application persisted in it.
    apps = {name: cache.load_applications(state["base_fp"][name])
            for name in state["suite"]}
    name_of = {fp: n for n, fp in state["base_fp"].items()}
    nodes = sum(_n_ands(state, name_of[c], src) for _t, c, src, _x in cache.persisted)
    n_apps = len(cache.persisted)
    ctx.counters["applications"] = n_apps
    return common.Window(
        start_ns=t0, end_ns=t1, attempted=n_apps, failed=0,
        end_to_end={"cha_nodes_per_s": nodes / ((t1 - t0) / 1e9)},
        state=dict(apps=apps),
    )


def _aig_file(state: dict, fp: str):
    hits = list(state["cache_dir"].glob(f"v*/aigs/{fp}.json"))
    return hits[0] if hits else None


def _load(state: dict, name: str, fp: str) -> dict | None:
    if fp == state["base_fp"][name]:
        return state["nets"][name]
    path = _aig_file(state, fp)
    if path is None:
        return None
    with open(path) as f:
        return json.load(f)


def _n_ands(state: dict, name: str, fp: str) -> int:
    d = _load(state, name, fp)
    return 0 if d is None else len(d["f0"]) - 1 - d["n_pis"]


# ---------------------------------------------------------------------------
# Correctness: every application the window finished, against the
# reference transforms, simulation and gate mapping
# ---------------------------------------------------------------------------


def expected(ctx: common.Ctx) -> dict[str, dict[tuple[str, str], str]]:
    """Per circuit, the reference's output fingerprint of each (source
    fingerprint, transform) on the recipes' paths."""
    data = common.load_json(ctx.root / ctx.config["expected_outputs"])
    out = {}
    for name in ctx.config["circuits"]:
        fps = data["circuits"][name]
        out[name] = {(fps[",".join(r[:-1])], r[-1]): fps[",".join(r)]
                     for r in ref.recipes(ctx.config["recipes"])[1:]}
    return out


def score(ctx: common.Ctx, state: dict, apps: dict) -> tuple[int, int, int]:
    """(applications whose output is not the reference's, applications
    whose output is not equivalent to their input or not what they claim
    to be, applications whose statistics are not their output's)."""
    want = expected(ctx)
    rng = ctx.rng(4)
    words = ctx.traffic["check_words"]
    wrong = bad_fn = bad_stats = 0
    for name, per in apps.items():
        for (src_fp, t), (out_fp, stats) in sorted(per.items()):
            wrong += want[name].get((src_fp, t)) != out_fp
            src, out = _load(state, name, src_fp), _load(state, name, out_fp)
            if (src is None or out is None or fingerprint(out) != out_fp
                    or ref.check_structure(out) is not None
                    or out["n_pis"] != src["n_pis"]
                    or len(out["pos"]) != len(src["pos"])):
                bad_fn += 1
                continue
            pats = rng.integers(0, np.iinfo(np.uint64).max, (src["n_pis"], words),
                                dtype=np.uint64, endpoint=True)
            if not np.array_equal(ref.simulate(src, pats), ref.simulate(out, pats)):
                bad_fn += 1
            if stats != ref.gate_stats(out):
                bad_stats += 1
    return wrong, bad_fn, bad_stats


def check(ctx: common.Ctx, state: dict, win: common.Window) -> list[common.Check]:
    apps = {name: {k: (o, None if s is None else s.to_dict())
                   for k, (o, s) in per.items()}
            for name, per in win.state["apps"].items()}
    wrong, bad_fn, bad_stats = score(ctx, state, apps)
    return [
        common.Check("wrong_outputs", wrong, WRONG_OUTPUT_LIMIT),
        common.Check("inequivalent_apps", bad_fn, INEQUIVALENT_LIMIT),
        common.Check("stats_mismatches", bad_stats, STATS_MISMATCH_LIMIT),
    ]
