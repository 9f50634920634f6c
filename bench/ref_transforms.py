"""Plain reference of the four AIG transforms (Ba, Rf, Rw, Rs).

Imports nothing of the program under test.  A separate copy of the
straightforward (one node at a time, python) form of the transforms as
the program states them at ``TRANSFORM_VERSION`` 2: the same decisions in
the same order, so the same source AIG gives the same output AIG, node
for node.  A change to the program's transforms shows as a mismatch here
(and must come with a new ``TRANSFORM_VERSION`` and new frozen data).

The characterization cell's check compares every application its window
persisted against `expected_outputs` of this module, frozen under
``bench/data`` by ``bench/freeze_cha.py``.
"""

from __future__ import annotations

import hashlib
import heapq
from functools import lru_cache

import numpy as np

TRANSFORM_VERSION = 2
CONST0, CONST1 = 0, 1


class Aig:
    """Fanin-literal AIG with structural hashing: literal ``2*node +
    phase``, node 0 the constant, nodes 1..n_pis the inputs."""

    def __init__(self, n_pis: int):
        self.f0 = [-1] * (1 + n_pis)
        self.f1 = [-1] * (1 + n_pis)
        self.n_pis = n_pis
        self.pos: list[int] = []
        self.strash: dict[tuple[int, int], int] = {}

    @classmethod
    def from_dict(cls, d: dict) -> "Aig":
        a = cls(int(d["n_pis"]))
        a.f0 = [int(x) for x in d["f0"]]
        a.f1 = [int(x) for x in d["f1"]]
        a.pos = [int(p) for p in d["pos"]]
        for n in range(a.n_pis + 1, a.n_nodes):
            a.strash[(a.f0[n], a.f1[n])] = n << 1
        return a

    def to_dict(self) -> dict:
        return dict(n_pis=self.n_pis, f0=list(self.f0), f1=list(self.f1),
                    pos=list(self.pos))

    def fingerprint(self) -> str:
        h = hashlib.sha256()
        for part in ([self.n_pis], self.f0, self.f1, self.pos):
            h.update(np.asarray(part, dtype=np.int64).tobytes())
        return h.hexdigest()

    @property
    def n_nodes(self) -> int:
        return len(self.f0)

    @property
    def n_ands(self) -> int:
        return self.n_nodes - 1 - self.n_pis

    def is_and(self, n: int) -> bool:
        return n > self.n_pis

    def fanins(self, n: int) -> tuple[int, int]:
        return self.f0[n], self.f1[n]

    def g_and(self, a: int, b: int) -> int:
        if a == CONST0 or b == CONST0:
            return CONST0
        if a == CONST1:
            return b
        if b == CONST1:
            return a
        if a == b:
            return a
        if a == b ^ 1:
            return CONST0
        if a > b:
            a, b = b, a
        hit = self.strash.get((a, b))
        if hit is not None:
            return hit
        self.f0.append(a)
        self.f1.append(b)
        out = (len(self.f0) - 1) << 1
        self.strash[(a, b)] = out
        return out

    def g_or(self, a: int, b: int) -> int:
        return self.g_and(a ^ 1, b ^ 1) ^ 1

    def g_xor(self, a: int, b: int) -> int:
        return self.g_or(self.g_and(a, b ^ 1), self.g_and(a ^ 1, b))

    def g_mux(self, sel: int, t: int, f: int) -> int:
        return self.g_or(self.g_and(sel, t), self.g_and(sel ^ 1, f))

    def g_and_multi(self, lits) -> int:
        acc = CONST1
        for x in lits:
            acc = self.g_and(acc, x)
        return acc

    def g_or_multi(self, lits) -> int:
        acc = CONST0
        for x in lits:
            acc = self.g_or(acc, x)
        return acc

    def levels(self) -> np.ndarray:
        lv = np.zeros(self.n_nodes, dtype=np.int32)
        for n in range(self.n_pis + 1, self.n_nodes):
            lv[n] = 1 + max(lv[self.f0[n] >> 1], lv[self.f1[n] >> 1])
        return lv

    def fanout_counts(self) -> np.ndarray:
        fo = np.zeros(self.n_nodes, dtype=np.int64)
        for n in range(self.n_pis + 1, self.n_nodes):
            fo[self.f0[n] >> 1] += 1
            fo[self.f1[n] >> 1] += 1
        for p in self.pos:
            fo[p >> 1] += 1
        return fo

    def cone_nodes(self, root: int, leaves: set[int]) -> list[int]:
        """AND nodes of ``root``'s cone down to ``leaves``, fanins first."""
        seen: set[int] = set()
        out: list[int] = []
        stack = [root]
        while stack:
            n = stack.pop()
            if n in seen or n in leaves or not self.is_and(n):
                continue
            need = [m for m in (self.f0[n] >> 1, self.f1[n] >> 1)
                    if m not in seen and m not in leaves and self.is_and(m)]
            if need:
                stack.append(n)
                stack.extend(need)
            else:
                seen.add(n)
                out.append(n)
        return out

    def truth_table(self, root_lit: int, support, cone=None) -> int:
        """Truth table of ``root_lit`` over ``support`` (bit p is pattern
        p, support[i] driving bit i of the pattern index)."""
        k = len(support)
        full = (1 << (1 << k)) - 1
        vals = {0: 0}
        for i, s in enumerate(support):
            vals[s] = _elem_tt(i, k)
        if cone is None:
            cone = self.cone_nodes(root_lit >> 1, set(support))
        for n in cone:
            fa, fb = self.f0[n], self.f1[n]
            vals[n] = ((vals[fa >> 1] ^ (full if fa & 1 else 0))
                       & (vals[fb >> 1] ^ (full if fb & 1 else 0)))
        v = vals[root_lit >> 1]
        return v ^ full if root_lit & 1 else v

    def clone(self) -> "Aig":
        """The cones reachable from the outputs, re-strashed in order."""
        reach = reachable(self)
        new = Aig(self.n_pis)
        m = {0: CONST0, **{i: i << 1 for i in range(1, 1 + self.n_pis)}}
        for n in range(self.n_pis + 1, self.n_nodes):
            if reach[n]:
                fa, fb = self.f0[n], self.f1[n]
                m[n] = new.g_and(m[fa >> 1] ^ (fa & 1), m[fb >> 1] ^ (fb & 1))
        new.pos = [m[p >> 1] ^ (p & 1) for p in self.pos]
        return new


def reachable(aig: Aig) -> np.ndarray:
    reach = np.zeros(aig.n_nodes, dtype=bool)
    stack = [p >> 1 for p in aig.pos]
    while stack:
        n = stack.pop()
        if reach[n] or not aig.is_and(n):
            continue
        reach[n] = True
        stack.append(aig.f0[n] >> 1)
        stack.append(aig.f1[n] >> 1)
    return reach


def _fresh(aig: Aig) -> tuple[Aig, dict[int, int]]:
    return Aig(aig.n_pis), {0: CONST0, **{i: i << 1 for i in range(1, 1 + aig.n_pis)}}


def _finish(aig: Aig, new: Aig, mapping: dict[int, int]) -> Aig:
    """Outputs through ``mapping``; the smaller of the result and the input."""
    new.pos = [mapping[p >> 1] ^ (p & 1) for p in aig.pos]
    out = new.clone()
    return out if out.n_ands <= aig.n_ands else aig


# ---------------------------------------------------------------------------
# Truth tables and plan synthesis
# ---------------------------------------------------------------------------


def _tt_mask(k: int) -> int:
    return (1 << (1 << k)) - 1


@lru_cache(maxsize=None)
def _elem_tt(i: int, k: int) -> int:
    acc = 0
    for p in range(1 << k):
        if (p >> i) & 1:
            acc |= 1 << p
    return acc


def _cofactors(tt: int, i: int, k: int) -> tuple[int, int]:
    e, step = _elem_tt(i, k), 1 << i
    lo, hi = tt & (e ^ _tt_mask(k)), tt & e
    return lo | (lo << step), hi | (hi >> step)


_PLANS: dict[tuple[int, int], tuple[int, tuple]] = {}


def synth_plan(tt: int, k: int) -> tuple[int, tuple]:
    """(AND-node cost, plan) of a k-variable truth table: constants and
    literals free, else the cheapest Shannon split over the variables."""
    full = _tt_mask(k)
    tt &= full
    hit = _PLANS.get((tt, k))
    if hit is not None:
        return hit
    res = None
    if tt == 0:
        res = (0, ("const", 0))
    elif tt == full:
        res = (0, ("const", 1))
    else:
        for i in range(k):
            e = _elem_tt(i, k)
            if tt == e:
                res = (0, ("leaf", i))
                break
            if tt == e ^ full:
                res = (0, ("not", ("leaf", i)))
                break
    if res is None:
        for i in range(k):
            neg, pos = _cofactors(tt, i, k)
            if neg == pos:
                continue
            if neg == 0:
                c, p = synth_plan(pos, k)
                cand = (c + 1, ("and", ("leaf", i), p))
            elif pos == 0:
                c, p = synth_plan(neg, k)
                cand = (c + 1, ("and", ("not", ("leaf", i)), p))
            elif neg == full:
                c, p = synth_plan(pos, k)
                cand = (c + 1, ("or", ("not", ("leaf", i)), p))
            elif pos == full:
                c, p = synth_plan(neg, k)
                cand = (c + 1, ("or", ("leaf", i), p))
            elif neg == pos ^ full:
                c, p = synth_plan(neg, k)
                cand = (c + 3, ("xor", ("leaf", i), p))
            else:
                c0, p0 = synth_plan(neg, k)
                c1, p1 = synth_plan(pos, k)
                cand = (c0 + c1 + 3, ("mux", i, p1, p0))
            if res is None or cand[0] < res[0]:
                res = cand
    _PLANS[(tt, k)] = res
    return res


def build_plan(aig: Aig, plan: tuple, leaves) -> int:
    op = plan[0]
    if op == "const":
        return CONST1 if plan[1] else CONST0
    if op == "leaf":
        return leaves[plan[1]]
    if op == "not":
        return build_plan(aig, plan[1], leaves) ^ 1
    if op == "mux":
        return aig.g_mux(leaves[plan[1]], build_plan(aig, plan[2], leaves),
                         build_plan(aig, plan[3], leaves))
    a, b = build_plan(aig, plan[1], leaves), build_plan(aig, plan[2], leaves)
    return {"and": aig.g_and, "or": aig.g_or, "xor": aig.g_xor}[op](a, b)


def _mffc_size(aig: Aig, root: int, fanout: np.ndarray, cone: list[int]) -> int:
    """Cone nodes whose every fanout stays in the cone (root included)."""
    refs: dict[int, int] = {}
    for n in cone:
        for f in aig.fanins(n):
            refs[f >> 1] = refs.get(f >> 1, 0) + 1
    return sum(1 for n in cone if n == root or refs.get(n, 0) >= fanout[n])


# ---------------------------------------------------------------------------
# Balance
# ---------------------------------------------------------------------------


def balance(aig: Aig) -> Aig:
    """Each maximal single-fanout AND tree rebuilt by pairing its two
    lowest-level leaves first."""
    new, mapping = _fresh(aig)
    level: dict[int, int] = {}
    fanout = aig.fanout_counts()

    def leaves_of(n: int, out: list[int]) -> None:
        for f in aig.fanins(n):
            if f & 1 == 0 and aig.is_and(f >> 1) and fanout[f >> 1] == 1:
                leaves_of(f >> 1, out)
            else:
                out.append(f)

    reach = reachable(aig)
    for n in range(aig.n_pis + 1, aig.n_nodes):
        if not reach[n]:
            continue
        leaves: list[int] = []
        leaves_of(n, leaves)
        lits = [mapping[f >> 1] ^ (f & 1) for f in leaves]
        ordered = sorted((level.get(x >> 1, 0), i, x) for i, x in enumerate(lits))
        h = [(lv, i, x) for i, (lv, _, x) in enumerate(ordered)]
        heapq.heapify(h)
        cnt = len(h)
        while len(h) > 1:
            la, _, a = heapq.heappop(h)
            lb, _, b = heapq.heappop(h)
            out = new.g_and(a, b)
            lv = max(la, lb) + 1
            level[out >> 1] = lv
            cnt += 1
            heapq.heappush(h, (lv, cnt, out))
        mapping[n] = h[0][2] if h else CONST1
    new.pos = [mapping[p >> 1] ^ (p & 1) for p in aig.pos]
    return new.clone()


# ---------------------------------------------------------------------------
# Rewrite
# ---------------------------------------------------------------------------


def _cuts(aig: Aig, k: int, max_cuts: int) -> list[list[frozenset[int]]]:
    cuts: list[list[frozenset[int]]] = [[] for _ in range(aig.n_nodes)]
    for n in range(1, 1 + aig.n_pis):
        cuts[n] = [frozenset((n,))]
    for n in range(aig.n_pis + 1, aig.n_nodes):
        na, nb = aig.f0[n] >> 1, aig.f1[n] >> 1
        got: set[frozenset[int]] = set()
        merged: list[frozenset[int]] = []
        for c1 in (cuts[na] if na else [frozenset()]):
            for c2 in (cuts[nb] if nb else [frozenset()]):
                u = c1 | c2
                if len(u) <= k and u not in got:
                    got.add(u)
                    merged.append(u)
        merged.sort(key=len)
        cuts[n] = merged[: max_cuts - 1] + [frozenset((n,))]
    return cuts


def rewrite(aig: Aig, k: int = 4, max_cuts: int = 8) -> Aig:
    """Each node's cone over its best k-cut replaced by a synthesized
    cone that adds fewer nodes than the cut's MFFC frees."""
    cuts = _cuts(aig, k, max_cuts)
    fanout = aig.fanout_counts()
    new, mapping = _fresh(aig)
    reach = reachable(aig)
    for n in range(aig.n_pis + 1, aig.n_nodes):
        if not reach[n]:
            continue
        fa, fb = aig.fanins(n)
        mapping[n] = new.g_and(mapping[fa >> 1] ^ (fa & 1), mapping[fb >> 1] ^ (fb & 1))
        best_gain, best = 0, None
        for cut in cuts[n]:
            if len(cut) < 2 or n in cut or any(m not in mapping for m in cut):
                continue
            support = sorted(cut)
            cone = aig.cone_nodes(n, set(cut))
            cost, plan = synth_plan(aig.truth_table(n << 1, support, cone), len(support))
            gain = _mffc_size(aig, n, fanout, cone) - cost
            if gain > best_gain:
                best_gain, best = gain, (plan, [mapping[m] for m in support])
        if best is not None:
            mapping[n] = build_plan(new, *best)
    return _finish(aig, new, mapping)


# ---------------------------------------------------------------------------
# Refactor
# ---------------------------------------------------------------------------


def _reconv_cut(aig: Aig, root: int, max_leaves: int) -> list[int]:
    leaves = {root}
    while True:
        best_leaf = best_cost = best_new = None
        for lf in leaves:
            if not aig.is_and(lf):
                continue
            newset = (leaves - {lf}) | {aig.f0[lf] >> 1, aig.f1[lf] >> 1}
            cost = len(newset) - len(leaves)
            if len(newset) > max_leaves:
                continue
            if best_cost is None or cost < best_cost:
                best_leaf, best_cost, best_new = lf, cost, newset
        if best_leaf is None:
            break
        leaves = best_new
        if best_cost >= 0 and len(leaves) >= max_leaves:
            break
    return sorted(leaves)


@lru_cache(maxsize=None)
def _isop(tt: int, care: int, k: int) -> tuple[tuple[int, int], ...]:
    """Minato-Morreale irredundant SOP as (positive mask, negative mask)
    cubes."""
    full = _tt_mask(k)
    tt &= full
    care &= full
    if care == 0 or tt & care == 0:
        return ()
    if tt & care == care:
        return ((0, 0),)
    i = -1
    for j in range(k - 1, -1, -1):
        t0, t1 = _cofactors(tt, j, k)
        c0, c1 = _cofactors(care, j, k)
        if t0 != t1 or c0 != c1:
            i = j
            break
    if i < 0:
        return ((0, 0),) if tt & care else ()
    t0, t1 = _cofactors(tt, i, k)
    c0, c1 = _cofactors(care, i, k)
    isop0 = _isop(t0 & ~(t1 & c1), c0, k)
    isop1 = _isop(t1 & ~(t0 & c0), c1, k)
    cov0, cov1 = _cover_tt(isop0, k), _cover_tt(isop1, k)
    rem = (t0 & c0 & ~cov0) | (t1 & c1 & ~cov1)
    isop2 = _isop(rem, (c0 & ~cov0) | (c1 & ~cov1), k)
    return (tuple((p, m | (1 << i)) for p, m in isop0)
            + tuple((p | (1 << i), m) for p, m in isop1) + isop2)


def _cover_tt(cubes, k: int) -> int:
    full = _tt_mask(k)
    acc = 0
    for pos, neg in cubes:
        c = full
        for i in range(k):
            if pos & (1 << i):
                c &= _elem_tt(i, k)
            elif neg & (1 << i):
                c &= full ^ _elem_tt(i, k)
        acc |= c
    return acc


def _factor(aig: Aig, cubes: list[tuple[int, int]], leaves: list[int]) -> int:
    """Algebraic factoring of an SOP by its most common literal."""
    if not cubes:
        return CONST0
    if cubes == [(0, 0)]:
        return CONST1

    def lits(c):
        return [leaves[i] if c[0] & (1 << i) else leaves[i] ^ 1
                for i in range(len(leaves)) if (c[0] | c[1]) & (1 << i)]

    if len(cubes) == 1:
        return aig.g_and_multi(lits(cubes[0]))
    count: dict[int, int] = {}
    for pos, neg in cubes:
        for i in range(len(leaves)):
            if pos & (1 << i):
                key = (i + 1) << 1
            elif neg & (1 << i):
                key = ((i + 1) << 1) ^ 1
            else:
                continue
            count[key] = count.get(key, 0) + 1
    best_key, best_cnt = None, 1
    for key, c in count.items():
        if c > best_cnt:
            best_key, best_cnt = key, c
    if best_key is None:
        return aig.g_or_multi([aig.g_and_multi(lits(c)) for c in cubes])
    i, is_neg = (best_key >> 1) - 1, best_key & 1
    bit = 1 << i
    with_lit = [(p, m & ~bit) if is_neg else (p & ~bit, m)
                for p, m in cubes if (m if is_neg else p) & bit]
    without = [(p, m) for p, m in cubes if not (m if is_neg else p) & bit]
    quot = _factor(aig, with_lit, leaves)
    rest = _factor(aig, without, leaves) if without else CONST0
    return aig.g_or(aig.g_and(leaves[i] ^ is_neg, quot), rest)


def refactor(aig: Aig, max_leaves: int = 10) -> Aig:
    """At multi-fanout or every third level's nodes, the cone over a
    reconvergence-driven cut refactored from its ISOP when that adds no
    more nodes than the cone's MFFC frees."""
    fanout = aig.fanout_counts()
    new, mapping = _fresh(aig)
    reach = reachable(aig)
    lv = aig.levels()
    for n in range(aig.n_pis + 1, aig.n_nodes):
        if not reach[n]:
            continue
        fa, fb = aig.fanins(n)
        mapping[n] = new.g_and(mapping[fa >> 1] ^ (fa & 1), mapping[fb >> 1] ^ (fb & 1))
        if fanout[n] < 2 and lv[n] % 3 != 0:
            continue
        leaves = _reconv_cut(aig, n, max_leaves)
        k = len(leaves)
        if k < 3 or n in leaves or k > 12:
            continue
        cone = aig.cone_nodes(n, set(leaves))
        cubes = list(_isop(aig.truth_table(n << 1, leaves, cone), _tt_mask(k), k))
        old_cost = _mffc_size(aig, n, fanout, cone)
        est = sum(bin(p | q).count("1") for p, q in cubes) + max(0, len(cubes) - 1)
        if est >= old_cost + 2:
            continue
        before = new.n_ands
        cand = _factor(new, cubes, [mapping[m] for m in leaves])
        if new.n_ands - before <= old_cost:
            mapping[n] = cand
    return _finish(aig, new, mapping)


# ---------------------------------------------------------------------------
# Resub
# ---------------------------------------------------------------------------


def resub(aig: Aig, n_words: int = 32, seed: int = 7) -> Aig:
    """Nodes merged into an earlier node of equal (or complemented)
    random-simulation signature whose truth tables agree exactly over
    their joint structural support (at most 14 inputs)."""
    if aig.n_pis == 0 or aig.n_ands == 0:
        return aig
    rng = np.random.default_rng(seed)
    pats = rng.integers(0, 1 << 63, size=(aig.n_pis, n_words),
                        dtype=np.int64).astype(np.uint64)
    full = np.uint64(0xFFFFFFFFFFFFFFFF)
    sig = np.zeros((aig.n_nodes, n_words), dtype=np.uint64)
    sig[1:1 + aig.n_pis] = pats
    for n in range(aig.n_pis + 1, aig.n_nodes):
        fa, fb = aig.fanins(n)
        sig[n] = ((sig[fa >> 1] ^ (full if fa & 1 else np.uint64(0)))
                  & (sig[fb >> 1] ^ (full if fb & 1 else np.uint64(0))))
    buckets: dict[bytes, list[int]] = {}
    for n in range(1, aig.n_nodes):
        buckets.setdefault(sig[n].tobytes(), []).append(n)
    sup: list[set[int] | None] = [set() for _ in range(aig.n_nodes)]
    for n in range(1, 1 + aig.n_pis):
        sup[n] = {n}
    for n in range(aig.n_pis + 1, aig.n_nodes):
        sa, sb = sup[aig.f0[n] >> 1], sup[aig.f1[n] >> 1]
        u = None if sa is None or sb is None else sa | sb
        sup[n] = None if u is None or len(u) > 14 else u
    replace: dict[int, int] = {}
    for n in range(aig.n_pis + 1, aig.n_nodes):
        key = sig[n].tobytes()
        cands = ([m for m in buckets.get(key, []) if m < n]
                 + [m for m in buckets.get((sig[n] ^ full).tobytes(), []) if m < n])
        for m in cands:
            neg = sig[m].tobytes() != key
            if sup[n] is None or sup[m] is None:
                continue
            s = sorted(sup[n] | sup[m])
            if len(s) > 14:
                continue
            tt_n, tt_m = aig.truth_table(n << 1, s), aig.truth_table(m << 1, s)
            if tt_n == tt_m and not neg:
                replace[n] = m << 1
                break
            if tt_n == tt_m ^ _tt_mask(len(s)) and neg:
                replace[n] = (m << 1) ^ 1
                break
    if not replace:
        return aig
    new, mapping = _fresh(aig)
    for n in range(aig.n_pis + 1, aig.n_nodes):
        if n in replace:
            r = replace[n]
            mapping[n] = mapping[r >> 1] ^ (r & 1)
        else:
            fa, fb = aig.fanins(n)
            mapping[n] = new.g_and(mapping[fa >> 1] ^ (fa & 1), mapping[fb >> 1] ^ (fb & 1))
    return _finish(aig, new, mapping)


TRANSFORMS = {"Ba": balance, "Rf": refactor, "Rw": rewrite, "Rs": resub}


def expected_outputs(d: dict, recipes) -> dict[str, str]:
    """Output fingerprint of every recipe (``","``-joined; ``""`` the
    input itself) on a fanin-literal AIG, each recipe applied step by step
    from its prefix's output."""
    base = Aig.from_dict(d)
    out: dict[tuple[str, ...], Aig] = {(): base}
    applied: dict[tuple[str, str], Aig] = {}
    for r in sorted((tuple(r) for r in recipes), key=lambda r: (len(r), r)):
        for i in range(1, len(r) + 1):
            p = r[:i]
            if p in out:
                continue
            src = out[p[:-1]]
            key = (src.fingerprint(), p[-1])
            if key not in applied:
                applied[key] = TRANSFORMS[p[-1]](src)
            out[p] = applied[key]
    return {",".join(r): a.fingerprint() for r, a in out.items()}

