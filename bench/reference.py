"""Plain reference for the benchmark's correctness checks.

Imports nothing of the program under test.  Two halves, mirroring the
program's:

* the back half: the rCiM cycle schedule ("list" discipline), the
  "physical" energy decomposition and the three-tier admissibility rule
  of Algorithm I, written out over plain arrays.  ``xp`` picks the array
  module and ``dtype`` the float precision, so the same code is the
  float64 reference (numpy, on the host) and the lower-precision control
  (float32, on the device through ``jax.numpy``);
* the front half: bit-parallel simulation of an AIG given as fanin
  literal arrays, and its NAND2/NOR2/NOT mapping statistics, for the
  characterization cell's equivalence and statistics checks.

The arithmetic follows the paper's model (arXiv:2411.09546, section
III-D/IV-A) as the program states it; it is a separate copy, so a change
to the program's model shows as a mismatch here.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

OP_TYPES = ("nand", "nor", "inv")
BITS_PER_GATE = 4
#: (rows, cols) of one macro per library macro size in KB (the paper's
#: wide organisation: more columns, more sense amplifiers).
LIBRARY_GEOMETRY = {4: (256, 128), 8: (256, 256), 16: (256, 512), 32: (256, 1024)}
#: EnergyModel field order; per-op fields carry (nand, nor, inv).
MODEL_FIELDS = (
    "f_clk_hz", "e_op_fj", "e_op_marginal_fj", "writeback_fj_nonresonant",
    "resonance_recycle_eta", "p_ctrl_mw", "e_macro_cycle_fj",
    "e_col_cycle_fj", "alpha_mw_per_level", "bitcell_um2",
    "periphery_overhead", "pipeline_utilization",
)
PER_OP_FIELDS = ("e_op_fj", "e_op_marginal_fj")


# ---------------------------------------------------------------------------
# Design space
# ---------------------------------------------------------------------------


def topologies(spec: dict) -> list[dict]:
    """Topologies of a configuration's ``topologies`` block, in the
    order the program is given them.

    ``{"kind": "library", "macro_kb": [...], "macro_counts": [...]}`` or
    ``{"kind": "grid", "rows": [...], "cols": [...], "macro_counts": [...]}``.
    """
    out = []
    if spec["kind"] == "library":
        for kb in spec["macro_kb"]:
            rows, cols = LIBRARY_GEOMETRY[kb]
            for m in spec["macro_counts"]:
                out.append(dict(name=f"({kb}KB)x{m}", rows=rows, cols=cols,
                                n_macros=m, total_kb=kb * m))
    elif spec["kind"] == "grid":
        for r in spec["rows"]:
            for c in spec["cols"]:
                if (r * c) % 8192:
                    continue
                for m in spec["macro_counts"]:
                    if m != 1 and m % 3:
                        continue
                    out.append(dict(name=f"({r}x{c})x{m}", rows=r, cols=c,
                                    n_macros=m, total_kb=(r * c // 8192) * m))
    else:
        raise ValueError(f"unknown topology kind {spec['kind']!r}")
    return out


def recipes(spec: dict) -> list[tuple[str, ...]]:
    """The baseline ``()`` then every ordered recipe of distinct
    transforms up to ``max_length`` (64 for four transforms)."""
    names = spec["transforms"]
    out: list[tuple[str, ...]] = [()]
    for r in range(1, spec["max_length"] + 1):
        out.extend(itertools.permutations(names, r))
    return out


# ---------------------------------------------------------------------------
# Back half: schedule, energy, selection
# ---------------------------------------------------------------------------


def workload(stats: list[dict]) -> dict:
    """Per-recipe totals from AigStats dicts: levels, op counts, gates."""
    ops = np.zeros((len(stats), 3), dtype=np.int64)
    for i, s in enumerate(stats):
        for lvl in s["ops_per_level"]:
            for j, t in enumerate(OP_TYPES):
                ops[i, j] += int(lvl.get(t, 0))
    gates = ops.sum(axis=1)
    return dict(
        n_levels=np.array([int(s["n_levels"]) for s in stats], dtype=np.int64),
        ops=ops,
        gates=gates,
    )


def schedule(work: dict, topos: list[dict]) -> dict:
    """The "list" (ASAP, width-bound) schedule of every (topology, recipe)
    pair: cycles, active macro-cycles and capacity/row feasibility, all
    exact integers, shaped (T, R)."""
    n_t, n_r = len(topos), len(work["n_levels"])
    cycles = np.zeros((n_t, n_r), dtype=np.int64)
    active = np.zeros((n_t, n_r), dtype=np.int64)
    fits = np.zeros((n_t, n_r), dtype=bool)
    for ti, t in enumerate(topos):
        w = t["cols"] // 2
        m = t["n_macros"]
        for ri in range(n_r):
            ops = [int(x) for x in work["ops"][ri]]
            depth = int(work["n_levels"][ri])
            if m == 1:
                width = sum(math.ceil(o / w) for o in ops if o)
                act = width
            else:
                k = 1 if m == 3 else m // 3
                per = [math.ceil(o / (w * k)) for o in ops if o]
                width = max(per) if per else 0
                act = sum(p * k for p in per)
            cycles[ti, ri] = max(depth, width) + 1
            active[ti, ri] = act
            rows_needed = 3 * math.ceil(max(1, width) / max(1, depth)) + 2
            total_bits = t["total_kb"] * 1024 * 8
            fits[ti, ri] = (BITS_PER_GATE * int(sum(ops)) <= total_bits
                            and rows_needed <= t["rows"])
    return dict(cycles=cycles, active=active, fits=fits)


def energy(work: dict, topos: list[dict], sched: dict, model: dict,
           xp=np, dtype=np.float64) -> tuple:
    """Physical-mode energy (nJ) and latency (ns) of every design,
    shaped (V, T, R), in ``dtype`` on array module ``xp``.

    ``model``: every EnergyModel field as a (V,) array, per-op fields
    (V, 3)."""
    f = lambda a: xp.asarray(np.asarray(a), dtype=dtype)  # noqa: E731
    cycles = f(sched["cycles"])[None]                      # (1, T, R)
    active = f(sched["active"])[None]
    cols = f([t["cols"] for t in topos])[None, :, None]     # (1, T, 1)
    ops = f(work["ops"])                                   # (R, 3)
    f_clk = f(model["f_clk_hz"])[:, None, None]            # (V, 1, 1)
    t_ns = cycles / f_clk * f(1e9)
    e_ops = (ops[None] * f(model["e_op_marginal_fj"])[:, None, :]).sum(-1)
    e_ops = e_ops[:, None, :]                              # (V, 1, R)
    e_ctrl = (f(model["p_ctrl_mw"])[:, None, None] * f(1e-3)
              * (t_ns * f(1e-9)) * f(1e15))
    e_macro = active * (f(model["e_macro_cycle_fj"])[:, None, None]
                        + f(model["e_col_cycle_fj"])[:, None, None] * cols)
    e_nj = (e_ctrl + e_macro + e_ops) * f(1e-6)
    return e_nj, t_ns


def capacity_feasible(total_bits: np.ndarray, min_gates: int,
                      within: np.ndarray | None = None) -> np.ndarray:
    """Algorithm I line 9: topologies holding 4 bits per gate of the
    smaller optimal AIG; the largest candidate when none does.  With a
    memory budget (``within``) the rule runs inside the budget."""
    cand = np.ones(len(total_bits), dtype=bool) if within is None else within
    feas = (total_bits >= BITS_PER_GATE * min_gates) & cand
    if not feas.any():
        feas = np.zeros(len(total_bits), dtype=bool)
        feas[int(np.argmax(np.where(cand, total_bits, -1)))] = True
    return feas


def min_gates(work: dict) -> int:
    """Gate count of the smaller of the optimal-ops and optimal-levels
    recipes (first in recipe order on ties)."""
    g, lv = work["gates"], work["n_levels"]
    r_gate = min(range(len(g)), key=lambda r: (int(g[r]), int(lv[r])))
    r_level = min(range(len(g)), key=lambda r: (int(lv[r]), int(g[r])))
    return min(int(g[r_gate]), int(g[r_level]))


def select(e_nj: np.ndarray, t_ns: np.ndarray, fits: np.ndarray,
           feasible: np.ndarray, max_latency_ns: float | None = None,
           within: np.ndarray | None = None) -> np.ndarray:
    """Algorithm I line 14 per variant: the lowest energy among
    admissible designs (fits, capacity-feasible, within the latency
    bound), else among designs that fit, else among all finite ones;
    lowest topology-major flat index on ties.  Designs outside a memory
    budget take part in no tier.  Returns (V,) flat indices t * R + r."""
    e = np.asarray(e_nj, dtype=np.float64)
    v, n_t, n_r = e.shape
    ok = np.isfinite(e)
    if within is not None:
        ok &= within[None, :, None]
    tier2 = ok & fits[None]
    tier1 = tier2 & feasible[None, :, None]
    if max_latency_ns is not None:
        tier1 &= np.asarray(t_ns) <= max_latency_ns
    out = np.zeros(v, dtype=np.int64)
    for i in range(v):
        for tier in (tier1[i], tier2[i], ok[i]):
            if tier.any():
                out[i] = int(np.argmin(np.where(tier, e[i], np.inf).ravel()))
                break
        else:
            raise ValueError("no finite energy for a variant")
    return out


# ---------------------------------------------------------------------------
# Front half: AIG simulation and gate-mapping statistics
# ---------------------------------------------------------------------------


def levels(n_pis: int, f0: list[int], f1: list[int]) -> np.ndarray:
    lv = np.zeros(len(f0), dtype=np.int64)
    for n in range(n_pis + 1, len(f0)):
        lv[n] = 1 + max(lv[f0[n] >> 1], lv[f1[n] >> 1])
    return lv


def check_structure(d: dict) -> str | None:
    """Why a fanin-literal AIG is malformed, or None when it is sound:
    fanins precede their node, literals address existing nodes."""
    n_pis, f0, f1, pos = d["n_pis"], d["f0"], d["f1"], d["pos"]
    n = len(f0)
    if len(f1) != n or n < n_pis + 1:
        return "fanin arrays disagree with the PI count"
    for node in range(n_pis + 1, n):
        if not (0 <= f0[node] >> 1 < node and 0 <= f1[node] >> 1 < node):
            return f"node {node} reads a later or missing node"
    if any(not 0 <= p >> 1 < n for p in pos):
        return "an output addresses a missing node"
    return None


def simulate(d: dict, patterns: np.ndarray) -> np.ndarray:
    """Output words of a fanin-literal AIG for ``patterns`` (n_pis, W)
    uint64: node values level by level, complemented edges XOR'd."""
    n_pis, f0, f1, pos = d["n_pis"], d["f0"], d["f1"], d["pos"]
    n = len(f0)
    full = np.uint64(0xFFFFFFFFFFFFFFFF)
    vals = np.zeros((n, patterns.shape[1]), dtype=np.uint64)
    vals[1:1 + n_pis] = patterns
    a0 = np.asarray(f0, dtype=np.int64)
    a1 = np.asarray(f1, dtype=np.int64)
    lv = levels(n_pis, f0, f1)
    ands = np.arange(n_pis + 1, n)
    for level in range(1, int(lv.max(initial=0)) + 1):
        ns = ands[lv[ands] == level]
        if ns.size == 0:
            continue
        x = vals[a0[ns] >> 1] ^ np.where((a0[ns] & 1).astype(bool), full, np.uint64(0))[:, None]
        y = vals[a1[ns] >> 1] ^ np.where((a1[ns] & 1).astype(bool), full, np.uint64(0))[:, None]
        vals[ns] = x & y
    p = np.asarray(pos, dtype=np.int64)
    return vals[p >> 1] ^ np.where((p & 1).astype(bool), full, np.uint64(0))[:, None]


def gate_stats(d: dict) -> dict:
    """The NAND2/NOR2/NOT mapping of an AIG, as AigStats fields.

    Every AND node becomes one gate: NOR2 when both fanin edges are
    complemented (it computes the node), NAND2 otherwise (it computes the
    node's complement), with a NOT inserted the first time a consumer
    needs the phase not yet realised.  A gate's level is one more than
    its deepest input; constants and PIs sit at level 0."""
    n_pis, f0, f1, pos = d["n_pis"], d["f0"], d["f1"], d["pos"]
    have: dict[tuple[int, int], int] = {(0, 0): 0, (0, 1): 0}
    for n in range(1, n_pis + 1):
        have[(n, 0)] = 0
    gates: list[tuple[str, int]] = []

    def need(node: int, phase: int) -> int:
        if (node, phase) in have:
            return have[(node, phase)]
        src = have[(node, phase ^ 1)]
        gates.append(("inv", src))
        have[(node, phase)] = src + 1
        return src + 1

    for n in range(n_pis + 1, len(f0)):
        a, b = f0[n], f1[n]
        if (a & 1) and (b & 1):
            lv = max(need(a >> 1, 0), need(b >> 1, 0))
            gates.append(("nor", lv))
            have[(n, 0)] = lv + 1
        elif not (a & 1) and not (b & 1):
            lv = max(need(a >> 1, 0), need(b >> 1, 0))
            gates.append(("nand", lv))
            have[(n, 1)] = lv + 1
        else:
            pos_side, neg_side = (b, a) if (a & 1) else (a, b)
            lp = need(pos_side >> 1, 0)
            ln = need(neg_side >> 1, 1)
            lv = max(lp, ln)
            gates.append(("nand", lv))
            have[(n, 1)] = lv + 1
    for p in pos:
        need(p >> 1, p & 1)
    n_levels = max((lv + 1 for _, lv in gates), default=0)
    per_level = [dict(nand=0, nor=0, inv=0) for _ in range(n_levels)]
    for kind, lv in gates:
        per_level[lv][kind] += 1
    counts = {t: sum(1 for k, _ in gates if k == t) for t in OP_TYPES}
    return dict(
        n_pis=n_pis, n_pos=len(pos), n_ands=len(f0) - 1 - n_pis,
        n_levels=n_levels, ops_per_level=per_level,
        nand_count=counts["nand"], nor_count=counts["nor"],
        inv_count=counts["inv"],
    )
