"""Device-resident AIG cone simulation — the front half's bit-packed engine.

The transforms (core/transforms.py) spend their time simulating small
cones: exact truth tables over a cut's leaves (rewrite / refactor /
resub verification) and whole-graph random signatures (resub).  The
python path computes these one cone at a time on arbitrary-precision
ints; this module moves them onto the device as *batched bit-packed
simulation*, reusing the instruction-stream layout of
``kernels/cim_logic.py``:

  * `compile_aig` lowers the (already topologically ordered) AIG once
    into a ``[kind, a_row, b_row, out_row]`` int32 instruction stream
    where ``kind`` packs the two fanin complement bits
    (``out = (a ^ pa) & (b ^ pb)``) and rows are node indices — plus a
    *wave-packed* variant (independent same-level nodes grouped so one
    scan step evaluates a whole wave) and the per-node AIG levels.
  * `eval_tts` evaluates a *batch* of (roots, support) queries.  On the
    jnp engine each word-tier's queries are assembled into chunked
    **mega-programs**: every query's cone is laid out in a shared flat
    row space (row 0 = const0, then per query its support rows — pinned
    to elementary truth tables, exactly `Aig.truth_table`'s semantics —
    followed by its cone rows), and the concatenated instructions are
    wave-packed by global AIG level.  Device work is therefore
    proportional to the *useful* cone work, not batch x whole-graph.
  * `node_signatures` runs the whole-graph wave stream over random
    uint64 pattern words (viewed as uint32 lanes) — bit-identical to
    ``transforms._node_signatures``.

Two device engines share the host wrapper: the pure-jnp ``lax.scan``
mega-program engine (the CPU-CI workhorse — Pallas interpret mode
would crawl) and a Pallas kernel with the cim_logic VMEM-scratch
layout (one grid step per block of queries packed side by side along
the lanes, evaluated against the full graph; the scratch is the "SRAM
array" holding every node's packed table).  ``engine="auto"`` picks
Pallas on TPU for programs whose scratch fits VMEM (`_pallas_fits`),
jnp elsewhere; both are bit-exact against the python-int reference,
which CI and the property tests enforce.

Shape discipline: queries bucket into word tiers (k <= 5 / 10 / 14
support vars -> 1 / 32 / 512 uint32 words); mega-program chunks are
bounded by a per-tier instruction budget and padded to pow2 shapes so
the jit cache stays small.  Queries wider than `DEVICE_MAX_VARS` take
the host bigint path on the jnp engine — at 512 words per table
CPython's limb loops already run at memory speed.  The kernels reach
jax through `runtime.jax_env.setup`, whose persistent compilation
cache means only the first process on a machine pays the XLA compiles
— the cross-process cold-start cost this module exists to kill.  A
`TRACE_COUNTS` counter (same idiom as core/batch.py) lets tests pin
the trace count.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

from repro.analysis import registry as _registry
from repro.core.aig import Aig, _elementary_int, lit_node, lit_phase
from repro.runtime import jax_env, trace

#: Traced-call counters (incremented inside the traced function bodies, so
#: they count *compiles*, not calls) — same discipline as core/batch.py.
#: The Counter lives in the unified registry; this module re-exports it.
# repro: kernel-module
TRACE_COUNTS = _registry.TRACE_COUNTS


def trace_counts() -> dict[str, int]:
    """Snapshot of this module's jit trace counters (for tests /
    benchmarks) — scoped to the aig kernels, as it always was."""
    return _registry.trace_counts(module=__name__)


# (max vars, uint32 words) shape tiers for truth-table queries.  A query
# with k support vars lands in the smallest tier with 32 * words >= 2**k;
# its table occupies the low 2**k bits and the host masks the rest off.
_TIERS: tuple[tuple[int, int], ...] = ((5, 1), (10, 32), (14, 512))
#: Queries per Pallas call, per word tier (a multiple of the block's
#: queries-per-block, so the grid is whole).
_CHUNK = {1: 2048, 32: 128, 512: 16}
#: Lanes of a TPU vector register: a Pallas block row is
#: ``max(_LANES, words)`` wide, so ``_LANES // words`` queries share one.
_LANES = 128
#: VMEM the Pallas engine's whole-graph scratch may take at its widest
#: tier, and the scoped-VMEM limit the kernel asks Mosaic for (v5e has
#: 128 MiB of VMEM; the default scoped limit is 16 MiB).
_PALLAS_SCRATCH_BYTES = 32 << 20
_PALLAS_VMEM_LIMIT = 64 << 20

#: jnp mega-program shape knobs per word tier: instructions per wave and
#: the per-chunk instruction budget.  Wider waves amortize the per-step
#: scan overhead; the budget bounds carry memory and jit-shape diversity.
_MEGA_WAVE = {1: 1024, 32: 256}
_MEGA_BUDGET = {1: 1 << 17, 32: 1 << 14}
#: Queries with more support vars than this take the host bigint path on
#: the jnp engine: at 512 words per table, CPython's big-int AND/XOR (a C
#: loop over limbs) is already at memory speed and the device round trip
#: cannot win.  The Pallas engine keeps them (TPU lanes don't care).
DEVICE_MAX_VARS = 10

MAX_VARS = _TIERS[-1][0]


#: Instructions per wave of the level-packed stream (see `compile_aig`).
WAVE_WIDTH = 128


@dataclasses.dataclass(frozen=True)
class AigProgram:
    """One AIG lowered to the shared instruction stream.

    ``lits[2 n], lits[2 n + 1]`` are node ``n``'s two fanin literals
    (``row << 1 | complement``; rows are node indices, node 0 = const0,
    nodes 1..n_pis = PIs), for every node up to ``n_pad`` (a power of
    two).  Const0, the PIs and the padding nodes carry ``(0, 0)``, the
    AND of const0 with itself — the Pallas engine's flat stream.

    ``waves`` is the stream as ``[kind, a_row, b_row, out_row]``
    instructions (``kind`` = pa | (pb << 1), the fanin complement bits),
    *level-packed* for the jnp engine: nodes grouped by AIG level (same-level nodes never depend on each
    other), each level split into `WAVE_WIDTH`-wide waves, so one scan
    step evaluates up to 128 independent nodes and the scan length is
    ~depth, not ~n_nodes.  Wave count pads to a power of two.
    """

    lits: np.ndarray  # (2 * n_pad,) int32 — flat, for the Pallas engine
    waves: np.ndarray  # (n_waves_pad, wave_w, 4) int32 — jnp sig engine
    lv: np.ndarray  # (n_nodes,) int64 — AIG levels (mega wave packing)
    n_nodes: int
    n_pis: int
    n_pad: int


def _next_pow2(x: int, floor: int = 3) -> int:
    return 1 << max(floor, (x - 1).bit_length())


def compile_aig(aig: Aig) -> AigProgram:
    """Lower an AIG to the level-ordered instruction stream (host, once)."""
    with trace.span("aig_sim.compile", n_nodes=aig.n_nodes):
        n_nodes = aig.n_nodes
        n_pad = _next_pow2(n_nodes + 1)
        f0 = np.asarray(aig._f0, dtype=np.int64)
        f1 = np.asarray(aig._f1, dtype=np.int64)
        instrs = np.zeros((n_pad, 4), dtype=np.int32)
        # No-op padding: AND of const0 with itself, parked in the scratch row.
        instrs[:, 3] = n_pad - 1
        lo = aig.n_pis + 1
        n_ands = n_nodes - lo
        if n_ands > 0:
            a, b = f0[lo:], f1[lo:]
            instrs[:n_ands, 0] = (a & 1) | ((b & 1) << 1)
            instrs[:n_ands, 1] = a >> 1
            instrs[:n_ands, 2] = b >> 1
            instrs[:n_ands, 3] = np.arange(lo, n_nodes)
        lits = np.zeros((n_pad, 2), dtype=np.int32)
        lits[lo:n_nodes, 0] = f0[lo:]
        lits[lo:n_nodes, 1] = f1[lo:]

        # Pack into waves by capacity-constrained ASAP list scheduling: a node
        # goes into the first non-full wave after both fanins' waves.  The wave
        # width adapts to the graph's average level width (deep carry-chain
        # circuits get narrow waves), so the stream stays *dense* — total slots
        # ~ n_ands, steps ~ depth — and the scan's memory traffic is bounded by
        # useful work, not padding.  Padding slots replay the no-op (scratch-row
        # write of const0 — duplicates within a wave all store the same value).
        lv = np.asarray(aig.levels(), dtype=np.int64)
        if n_ands > 0:
            depth = max(1, int(lv.max()))
            wave_w = _next_pow2(min(WAVE_WIDTH, max(8, -(-n_ands // depth))))
            wave_of = np.full(n_nodes, -1, dtype=np.int64)
            fill: list[int] = []
            wave_id = np.zeros(n_ands, dtype=np.int64)
            col = np.zeros(n_ands, dtype=np.int64)
            for i in range(n_ands):
                node = lo + i
                w = max(wave_of[f0[node] >> 1], wave_of[f1[node] >> 1]) + 1
                while w < len(fill) and fill[w] >= wave_w:
                    w += 1
                while w >= len(fill):
                    fill.append(0)
                wave_of[node] = w
                wave_id[i] = w
                col[i] = fill[w]
                fill[w] += 1
            n_waves = len(fill)
        else:
            wave_w = 8
            n_waves = 0
        n_waves_pad = _next_pow2(n_waves + 1, floor=1)
        waves = np.zeros((n_waves_pad, wave_w, 4), dtype=np.int32)
        waves[:, :, 3] = n_pad - 1
        if n_ands > 0:
            waves[wave_id, col] = instrs[:n_ands]
        return AigProgram(
            lits=lits.reshape(-1),
            waves=waves,
            lv=lv,
            n_nodes=n_nodes,
            n_pis=aig.n_pis,
            n_pad=n_pad,
        )


@functools.lru_cache(maxsize=None)
def _elem_words(k_max: int) -> np.ndarray:
    """Elementary truth tables of ``k_max`` vars as (k_max, words) uint32,
    LSB-first pattern order — `Aig._elementary_int` bit-packed."""
    n_pat = 1 << k_max
    words = max(1, n_pat // 32)
    out = np.zeros((k_max, words), dtype=np.uint32)
    for i in range(k_max):
        v = _elementary_int(i, k_max)
        out[i] = np.frombuffer(v.to_bytes(words * 4, "little"), dtype="<u4")
    return out


@functools.lru_cache(maxsize=None)
def _dev_elem(k_max: int):
    """`_elem_words(k_max)` already resident on the device."""
    import jax.numpy as jnp

    return jnp.asarray(_elem_words(k_max))


def words_to_int(words: np.ndarray) -> int:
    """Little-endian uint32 words -> python int (LSB-first patterns)."""
    return int.from_bytes(np.ascontiguousarray(words, dtype="<u4").tobytes(), "little")


def _tier_for(k: int) -> tuple[int, int]:
    for k_max, w in _TIERS:
        if k <= k_max:
            return k_max, w
    raise ValueError(f"eval_tts limited to {MAX_VARS} support vars, got {k}")


# ---------------------------------------------------------------------------
# jnp engine — lax.scan over wave-packed instruction streams
# ---------------------------------------------------------------------------

_JNP_MEGA = None
_JNP_SIG = None


def _make_jnp_mega():
    """A fresh jit wrapper around the mega-program evaluator (fresh =
    empty trace cache, as the analyzer's counter check requires);
    production goes through `_jnp_mega_fn`'s process-wide cache."""
    jax_env.setup()
    import jax
    import jax.numpy as jnp

    def eval_mega(waves, pin_rows, elem, rootp):
        """Evaluate one mega-program (many concatenated cone programs).

        waves (L,M,4) i32 over a flat row space; pin_rows (N,) i32
        var-index-or--1; elem (K,W) u32; rootp (Q,) i32 packs each root
        query as ``row << 1 | phase``.  Returns (Q,W) u32.  Support rows
        hold elementary tables and are never written (cone membership
        excludes pinned nodes), so the step body is just
        gather-AND-scatter.
        """
        TRACE_COUNTS["aig_eval"] += 1
        vals0 = jnp.where(
            (pin_rows >= 0)[:, None],
            elem[jnp.clip(pin_rows, 0, elem.shape[0] - 1)],
            jnp.uint32(0),
        )  # (N, W)
        full = jnp.uint32(0xFFFFFFFF)

        def step(vals, ins):
            # ins (M, 4): one wave of independent instructions.
            kind, a, b, o = ins[:, 0], ins[:, 1], ins[:, 2], ins[:, 3]
            va = vals[a] ^ (full * (kind & 1).astype(jnp.uint32))[:, None]
            vb = vals[b] ^ (full * ((kind >> 1) & 1).astype(jnp.uint32))[:, None]
            return vals.at[o].set(va & vb), None

        vals, _ = jax.lax.scan(step, vals0, waves)
        phase = (full * (rootp & 1).astype(jnp.uint32))[:, None]
        return vals[rootp >> 1] ^ phase

    return jax.jit(eval_mega)


def _jnp_mega_fn():
    global _JNP_MEGA
    if _JNP_MEGA is None:
        _JNP_MEGA = _make_jnp_mega()
    return _JNP_MEGA


def _make_jnp_sig():
    """Fresh jit wrapper for the signature evaluator (see
    `_make_jnp_mega`)."""
    jax_env.setup()
    import jax
    import jax.numpy as jnp

    def sig_eval(waves, vals0):
        """waves (L,M,4) i32; vals0 (N,W) u32 with PI rows pre-placed."""
        TRACE_COUNTS["aig_sig"] += 1
        full = jnp.uint32(0xFFFFFFFF)

        def step(vals, ins):
            kind, a, b, o = ins[:, 0], ins[:, 1], ins[:, 2], ins[:, 3]
            va = vals[a] ^ (full * (kind & 1).astype(jnp.uint32))[:, None]
            vb = vals[b] ^ (full * ((kind >> 1) & 1).astype(jnp.uint32))[:, None]
            return vals.at[o].set(va & vb), None

        vals, _ = jax.lax.scan(step, vals0, waves)
        return vals

    return jax.jit(sig_eval)


def _jnp_sig_fn():
    global _JNP_SIG
    if _JNP_SIG is None:
        _JNP_SIG = _make_jnp_sig()
    return _JNP_SIG


# ---------------------------------------------------------------------------
# Pallas engine — cim_logic's VMEM-scratch layout, queries packed in lanes
# ---------------------------------------------------------------------------
#
# One grid step evaluates a block of queries against the whole graph.  A
# scratch row holds one node's table for every query of the block side by
# side (``words`` lanes each, ``_LANES // words`` queries per 128-lane row
# on the narrow tiers), so each instruction is one full-width row op.  The
# instruction stream, the per-block pinned nodes and the root literals are
# read as scalars, so they come in through SMEM (scalar prefetch); the
# pinned lanes' masks and elementary tables are VMEM rows.  Pinned nodes
# are sorted, so one pointer carried through the node loop finds them.


def _pallas_geometry(w: int) -> tuple[int, int]:
    """(row width in lanes, queries per block) for word tier ``w``."""
    width = max(_LANES, w)
    return width, width // w


def _pallas_fits(n_pad: int) -> bool:
    """Whether a program's whole-graph scratch fits `_PALLAS_SCRATCH_BYTES`
    at the widest tier — the rule by which ``engine="auto"`` picks the
    Pallas engine on a TPU.  (Its SMEM stream, 8 bytes per node, is then
    far inside the 1 MiB of SMEM.)"""
    width, _ = _pallas_geometry(_TIERS[-1][1])
    return n_pad * width * 4 <= _PALLAS_SCRATCH_BYTES


def _n_pin_slots(w: int) -> int:
    """Pinned-node slots per block: every query's support, a sentinel,
    rounded to the 8-row sublane tile."""
    k_max = next(km for km, tw in _TIERS if tw == w)
    _, qb = _pallas_geometry(w)
    return -(-(qb * k_max + 1) // 8) * 8


def _make_pallas_eval(interpret: bool):
    """A fresh jit wrapper around the Pallas evaluator; production goes
    through `_pallas_fn`, which derives ``interpret`` from the backend."""
    jax_env.setup()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(meta_ref, lits_ref, pins_ref, roots_ref, mask_ref, vals_ref,
               out_ref, scratch_ref, *, w: int, n_roots: int, n_slots: int):
        blk = pl.program_id(0)
        width = scratch_ref.shape[1]
        qb = width // w
        zero = jnp.zeros((1, width), jnp.int32)
        scratch_ref[0:1, :] = zero  # const0
        pbase = blk * n_slots

        def step(i, p):
            a = lits_ref[2 * i]
            b = lits_ref[2 * i + 1]
            va = scratch_ref[pl.ds(a >> 1, 1), :] ^ -(a & 1)
            vb = scratch_ref[pl.ds(b >> 1, 1), :] ^ -(b & 1)
            res = va & vb
            scratch_ref[pl.ds(i, 1), :] = res
            hit = pins_ref[pbase + p] == i

            @pl.when(hit)
            def _():
                m = mask_ref[pl.ds(p, 1), :]
                v = vals_ref[pl.ds(p, 1), :]
                scratch_ref[pl.ds(i, 1), :] = jnp.where(m != 0, v, res)

            return p + hit.astype(jnp.int32)

        jax.lax.fori_loop(0, meta_ref[0], step, jnp.int32(0))

        lane_q = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // w
        rbase = blk * qb * n_roots
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)
        for j in range(n_roots):

            def gather(q, acc, j=j):
                lit = roots_ref[rbase + q * n_roots + j]
                v = scratch_ref[pl.ds(lit >> 1, 1), :] ^ -(lit & 1)
                return jnp.where(lane_q == q, v, acc)

            out_ref[j : j + 1, :] = jax.lax.fori_loop(0, qb, gather, zero)

    @functools.partial(jax.jit, static_argnames=("w", "n_roots"))
    def eval_batch(meta, lits, pins, roots, mask, vals, w: int, n_roots: int):
        """meta (1,) i32 = [n_nodes]; lits (2 n_pad,) i32; pins (B S,) i32
        sorted pinned nodes per block; roots (B qb R,) i32 root
        literals; mask / vals (B S, width) i32 pinned lanes and their
        tables.  Returns (B R8, width) i32: row j of block b holds root
        j of every query in the block."""
        TRACE_COUNTS["aig_eval_pallas"] += 1
        n_pad = lits.shape[0] // 2
        width = mask.shape[1]
        n_slots = _n_pin_slots(w)
        n_blocks = pins.shape[0] // n_slots
        r8 = -(-n_roots // 8) * 8
        row = lambda b, *_: (b, 0)  # noqa: E731
        return pl.pallas_call(
            functools.partial(kernel, w=w, n_roots=n_roots, n_slots=n_slots),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n_blocks,),
                in_specs=[
                    pl.BlockSpec((n_slots, width), row),
                    pl.BlockSpec((n_slots, width), row),
                ],
                out_specs=pl.BlockSpec((r8, width), row),
                scratch_shapes=[pltpu.VMEM((n_pad, width), jnp.int32)],
            ),
            out_shape=jax.ShapeDtypeStruct((n_blocks * r8, width), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_PALLAS_VMEM_LIMIT,
            ),
            interpret=interpret,
        )(meta, lits, pins, roots, mask, vals)

    return eval_batch


_PALLAS_EVAL = None


def _pallas_fn():
    global _PALLAS_EVAL
    if _PALLAS_EVAL is None:
        _PALLAS_EVAL = _make_pallas_eval(jax_env.pallas_interpret())
    return _PALLAS_EVAL


def _resolve_engine(engine: str, prog: "AigProgram | None" = None) -> str:
    """``auto``: Pallas on a TPU when the program fits (`_pallas_fits`),
    the jnp engine otherwise."""
    if engine == "auto":
        import jax

        on_tpu = jax.default_backend() == "tpu"
        fits = prog is None or _pallas_fits(prog.n_pad)
        return "pallas" if on_tpu and fits else "jnp"
    if engine not in ("jnp", "pallas"):
        raise ValueError(f"unknown aig_sim engine {engine!r}")
    return engine


# ---------------------------------------------------------------------------
# Host API
# ---------------------------------------------------------------------------


def _row_map(n_nodes: int, item: np.ndarray, node: np.ndarray, row: np.ndarray):
    """The (query, node) -> row lookup of one mega-program, kept sparse.

    A node listed twice for one query maps to its last row, as a
    repeated support variable does on the python path.  Unmapped nodes
    read row 0 (const0): the python path would raise on such a read, and
    no caller makes one (cones are closed over their supports)."""
    flat = item * n_nodes + node
    order = np.argsort(flat, kind="stable")
    # a leading sentinel below every key stands for "unmapped"
    keys = np.concatenate(([-1], flat[order]))
    rows = np.concatenate(([0], row[order]))

    def row_of(q: np.ndarray, n: np.ndarray) -> np.ndarray:
        want = q * n_nodes + n
        pos = np.searchsorted(keys, want, side="right") - 1
        return np.where(keys[pos] == want, rows[pos], 0)

    return row_of


def _eval_mega_tier(
    aig: Aig,
    prog: AigProgram,
    items: Sequence[tuple[Sequence[int], Sequence[int]]],
    idxs: list[int],
    w: int,
    members: tuple[np.ndarray, np.ndarray],
    results: list,
) -> None:
    """Run one word tier's queries as mega-programs on the jnp engine.

    ``members`` holds the tier's cone pairs (`Aig.cone_pairs`), their
    query numbered by position in ``idxs``.

    Each chunk concatenates the per-query cone programs into one flat
    row space (row 0 = const0, then per query: k support rows pinned to
    elementary tables followed by its cone rows in topo order), so
    device work is proportional to the *useful* cone work — not to
    batch × whole-graph as a lock-step layout would be.  Instructions
    are wave-packed by global AIG level (fanins always have strictly
    smaller levels, and cross-query instructions are independent), which
    keeps waves dense: scan length ~ total instrs / wave width.
    """
    import jax.numpy as jnp

    k_max = next(km for km, tw in _TIERS if tw == w)
    dev_elem = _dev_elem(k_max)
    f0 = np.asarray(aig._f0, dtype=np.int64)  # repro: host-boundary
    f1 = np.asarray(aig._f1, dtype=np.int64)  # repro: host-boundary
    mem_b, mem_node = members
    sizes = np.bincount(mem_b, minlength=len(idxs))
    budget = _MEGA_BUDGET[w]
    wave_m = _MEGA_WAVE[w]
    fn = _jnp_mega_fn()

    chunks: list[list[int]] = []
    cur: list[int] = []
    acc = 0
    for pos in range(len(idxs)):
        s = int(sizes[pos])
        if cur and acc + s > budget:
            chunks.append(cur)
            cur, acc = [], 0
        cur.append(pos)
        acc += s
    if cur:
        chunks.append(cur)

    import itertools

    for chunk in chunks:
        with trace.span("aig_sim.pack") as span:
            # chunks are runs of consecutive positions
            lo, hi = np.searchsorted(mem_b, (chunk[0], chunk[-1] + 1))
            b_idx, node_idx = mem_b[lo:hi] - chunk[0], mem_node[lo:hi]
            counts = sizes[chunk[0] : chunk[-1] + 1]
            it = [items[idxs[p]] for p in chunk]
            k_b = np.array([len(s) for _, s in it], dtype=np.int64)  # repro: host-boundary
            r_b = np.array([len(r) for r, _ in it], dtype=np.int64)  # repro: host-boundary
            row_base = 1 + np.concatenate(([0], np.cumsum(k_b + counts)[:-1]))
            n_rows = int(1 + (k_b + counts).sum())
            n_rows_pad = _next_pow2(n_rows + 1, floor=10)
            # Support rows: pinned to elementary tables via the pin map.
            tot_k = int(k_b.sum())
            sup_nodes = np.fromiter(
                itertools.chain.from_iterable(s for _, s in it),
                dtype=np.int64,
                count=tot_k,
            )
            item_of_sup = np.repeat(np.arange(len(it)), k_b)
            koff = np.concatenate(([0], np.cumsum(k_b)[:-1]))
            var_idx = np.arange(tot_k) - np.repeat(koff, k_b)
            sup_rows = row_base[item_of_sup] + var_idx
            pin_rows = np.full(n_rows_pad, -1, dtype=np.int32)
            pin_rows[sup_rows] = var_idx
            starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            local = np.arange(len(b_idx)) - np.repeat(starts, counts)
            cone_rows = row_base[b_idx] + k_b[b_idx] + local
            row_of = _row_map(
                aig.n_nodes,
                np.concatenate((item_of_sup, b_idx)),
                np.concatenate((sup_nodes, node_idx)),
                np.concatenate((sup_rows, cone_rows)),
            )
            n_waves = 0
            if len(b_idx):
                f0n = f0[node_idx]
                f1n = f1[node_idx]
                kind = (f0n & 1) | ((f1n & 1) << 1)
                a_row = row_of(b_idx, f0n >> 1)
                b_row = row_of(b_idx, f1n >> 1)
                instr = np.stack([kind, a_row, b_row, cone_rows], axis=1).astype(
                    np.int32
                )
                # Wave-pack by global level, chopping each level into
                # wave_m-wide groups (same-level instrs never depend).
                lvn = prog.lv[node_idx]
                order = np.argsort(lvn, kind="stable")
                slv = lvn[order]
                lstarts = np.searchsorted(slv, slv, side="left")
                pos_in_lv = np.arange(len(order)) - lstarts
                # (level, sub-group) keys are non-decreasing in `order`, so
                # consecutive-difference cumsum numbers the waves directly.
                key = slv * (len(order) + 1) + pos_in_lv // wave_m
                wid = np.concatenate(([0], np.cumsum(np.diff(key) > 0)))
                n_waves = int(wid[-1]) + 1
            n_waves_pad = _next_pow2(n_waves + 1, floor=2)
            waves = np.zeros((n_waves_pad, wave_m, 4), dtype=np.int32)
            waves[:, :, 3] = n_rows_pad - 1  # no-op padding: scratch row <- 0
            if len(b_idx):
                waves[wid, pos_in_lv % wave_m] = instr[order]
            # Root queries: one output row per root literal.
            q_item = np.repeat(np.arange(len(it)), r_b)
            root_lits = np.fromiter(
                itertools.chain.from_iterable(r for r, _ in it),
                dtype=np.int64,
                count=int(r_b.sum()),
            )
            root_rows = row_of(q_item, root_lits >> 1)
            n_q = len(root_lits)
            n_q_pad = _next_pow2(n_q, floor=6)
            rootp = np.zeros(n_q_pad, dtype=np.int32)
            rootp[:n_q] = (root_rows.astype(np.int64) << 1) | (root_lits & 1)
            span.set_metadata(
                h2d_bytes=waves.nbytes + pin_rows.nbytes + rootp.nbytes
            )
        with trace.span("aig_sim.launch", engine="jnp", w=w, queries=len(chunk)):
            out = np.asarray(  # repro: host-boundary
                fn(
                    jnp.asarray(waves),
                    jnp.asarray(pin_rows),
                    dev_elem,
                    jnp.asarray(rootp),
                )
            )
        with trace.span("aig_sim.unpack"):
            qoff = np.concatenate(([0], np.cumsum(r_b)))
            if w == 1:
                flat = out[:n_q, 0].tolist()
                for bi, p in enumerate(chunk):
                    idx = idxs[p]
                    roots, support = items[idx]
                    mask = (1 << (1 << len(support))) - 1
                    base = int(qoff[bi])
                    results[idx] = tuple(
                        flat[base + ri] & mask for ri in range(len(roots))
                    )
            else:
                buf = np.ascontiguousarray(out[:n_q]).tobytes()
                nb = w * 4
                for bi, p in enumerate(chunk):
                    idx = idxs[p]
                    roots, support = items[idx]
                    mask = (1 << (1 << len(support))) - 1
                    base = int(qoff[bi])
                    results[idx] = tuple(
                        int.from_bytes(
                            buf[(base + ri) * nb : (base + ri + 1) * nb], "little"
                        )
                        & mask
                        for ri in range(len(roots))
                    )


def eval_tts(
    aig: Aig,
    items: Sequence[tuple[Sequence[int], Sequence[int]]],
    engine: str = "auto",
    program: AigProgram | None = None,
    members: tuple[np.ndarray, np.ndarray] | None = None,
) -> list[tuple[int, ...]]:
    """Batched exact truth tables: ``items[i] = (root_lits, support)``.

    Returns, per item, one python-int truth table per root literal —
    bit-identical to ``aig.truth_table(root_lit, support)`` (same
    LSB-first pattern order, same pinned-support semantics).

    On the jnp engine, queries with <= `DEVICE_MAX_VARS` support vars
    are bucketed by word tier and evaluated as chunked *mega-programs*
    (see `_eval_mega_tier`); wider queries take the host bigint path,
    where CPython's limb loops already run at memory speed.  ``members``
    may supply the items' cone pairs from `Aig.cone_pairs` (callers that
    already sized MFFCs have them); otherwise they are walked here.

    The Pallas engine evaluates every query against the whole graph
    (one grid step per block of lane-packed queries, VMEM scratch = the
    packed node array).
    """
    if not items:
        return []
    prog = program if program is not None else compile_aig(aig)
    engine = _resolve_engine(engine, prog)
    results: list[tuple[int, ...] | None] = [None] * len(items)
    if engine == "pallas":
        _eval_pallas(aig, prog, items, results)
        return results  # type: ignore[return-value]

    tiers: dict[int, list[int]] = {}
    for idx, (roots, support) in enumerate(items):
        k = len(support)
        if k > DEVICE_MAX_VARS:
            sup = list(support)
            results[idx] = tuple(aig.truth_table(rl, sup) for rl in roots)
        else:
            _, w = _tier_for(k)
            tiers.setdefault(w, []).append(idx)
    if tiers and members is None:
        members = aig.cone_pairs(
            [[rl >> 1 for rl in roots] for roots, _ in items],
            [support for _, support in items],
            prog.lv,
        )[:2]
    for w, idxs in tiers.items():
        pos_of = np.full(len(items), -1, dtype=np.int64)
        pos_of[idxs] = np.arange(len(idxs))
        tier_b = pos_of[members[0]]
        keep = tier_b >= 0
        _eval_mega_tier(
            aig, prog, items, idxs, w, (tier_b[keep], members[1][keep]), results
        )
    return results  # type: ignore[return-value]


def _eval_pallas(
    aig: Aig,
    prog: AigProgram,
    items: Sequence[tuple[Sequence[int], Sequence[int]]],
    results: list,
) -> None:
    import jax.numpy as jnp

    groups: dict[tuple[int, int], list[int]] = {}
    for idx, (roots, support) in enumerate(items):
        _, w = _tier_for(len(support))
        groups.setdefault((w, len(roots)), []).append(idx)

    fn = _pallas_fn()
    with trace.span("aig_sim.pack", h2d_bytes=prog.lits.nbytes):
        meta = jnp.full((1,), prog.n_nodes, dtype=jnp.int32)
        lits = jnp.asarray(prog.lits)
    for (w, n_roots), idxs in groups.items():
        width, qb = _pallas_geometry(w)
        chunk = _CHUNK[w]
        n_blocks = chunk // qb
        for lo in range(0, len(idxs), chunk):
            batch = idxs[lo : lo + chunk]
            with trace.span("aig_sim.pack") as span:
                ops = _pallas_operands(prog, items, batch, w, n_roots)
                span.set_metadata(h2d_bytes=sum(x.nbytes for x in ops))
            with trace.span("aig_sim.launch", engine="pallas", w=w,
                            queries=len(batch)):
                out = fn(meta, lits, *map(jnp.asarray, ops), w=w, n_roots=n_roots)
                out = np.asarray(out).view(np.uint32)  # repro: host-boundary
            with trace.span("aig_sim.unpack"):
                # (block, root, query-in-block, word) -> (query, root, word)
                out = out.reshape(n_blocks, -1, qb, w)[:, :n_roots]
                out = out.transpose(0, 2, 1, 3).reshape(chunk, n_roots, w)
                for bi, idx in enumerate(batch):
                    root_lits, support = items[idx]
                    tmask = (1 << (1 << len(support))) - 1
                    results[idx] = tuple(
                        words_to_int(out[bi, ri]) & tmask
                        for ri in range(len(root_lits))
                    )


def _pallas_operands(
    prog: AigProgram,
    items: Sequence[tuple[Sequence[int], Sequence[int]]],
    batch: list[int],
    w: int,
    n_roots: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host-side operands of one Pallas call over ``batch`` (padded to
    `_CHUNK`; padding queries pin nothing and read const0).

    Per block: the distinct pinned nodes in ascending order (then the
    sentinel ``n_pad``, which no node index reaches), and for each the
    lanes of the queries that pin it with those queries' elementary
    tables.  Roots are the flat ``(query, root)`` literals."""
    k_max = next(km for km, tw in _TIERS if tw == w)
    elem = _elem_words(k_max).view(np.int32)
    width, qb = _pallas_geometry(w)
    n_slots = _n_pin_slots(w)
    n_blocks = _CHUNK[w] // qb
    n_pad = prog.n_pad
    sups = [np.asarray(items[i][1], dtype=np.int64) for i in batch]  # repro: host-boundary
    sup_lens = np.array([len(s) for s in sups], dtype=np.int64)  # repro: host-boundary
    q_of = np.repeat(np.arange(len(batch)), sup_lens)
    node = np.concatenate(sups) if sups else np.zeros(0, np.int64)
    var = np.arange(len(node)) - np.repeat(np.cumsum(sup_lens) - sup_lens, sup_lens)
    blk = q_of // qb
    uniq, inv = np.unique(blk * n_pad + node, return_inverse=True)
    ublk = uniq // n_pad
    slot = np.arange(len(uniq)) - np.searchsorted(ublk, ublk)
    pins = np.full(n_blocks * n_slots, n_pad, dtype=np.int32)
    pins[ublk * n_slots + slot] = uniq % n_pad
    rows = (ublk * n_slots + slot)[inv][:, None]
    lanes = (q_of % qb)[:, None] * w + np.arange(w)
    mask = np.zeros((n_blocks * n_slots, width), dtype=np.int32)
    vals = np.zeros((n_blocks * n_slots, width), dtype=np.int32)
    mask[rows, lanes] = -1
    vals[rows, lanes] = elem[var]
    roots = np.zeros((n_blocks * qb, n_roots), dtype=np.int32)
    roots[: len(batch)] = [items[i][0] for i in batch]
    return pins, roots.reshape(-1), mask, vals


def eval_tt(
    aig: Aig,
    root_lit: int,
    support: Sequence[int],
    engine: str = "auto",
    program: AigProgram | None = None,
) -> int:
    """Single-query convenience wrapper around `eval_tts`."""
    return eval_tts(aig, [((root_lit,), list(support))], engine, program)[0][0]


def node_signatures(
    aig: Aig,
    patterns: np.ndarray,
    engine: str = "auto",
    program: AigProgram | None = None,
) -> np.ndarray:
    """Per-node random-simulation signatures on the device.

    ``patterns``: (n_pis, n_words) uint64.  Returns (n_nodes, n_words)
    uint64, bit-identical to ``transforms._node_signatures`` (the uint64
    words are simulated as pairs of uint32 lanes).
    """
    _resolve_engine(engine)  # validate / pick (sig path is jnp on CPU+TPU)
    prog = program if program is not None else compile_aig(aig)
    import jax.numpy as jnp

    with trace.span("aig_sim.pack") as span:
        patterns = np.asarray(patterns, dtype=np.uint64)  # repro: host-boundary
        n_words = patterns.shape[1]
        vals0 = np.zeros((prog.n_pad, 2 * n_words), dtype=np.uint32)
        vals0[1 : 1 + prog.n_pis] = patterns.view("<u4")
        span.set_metadata(h2d_bytes=prog.waves.nbytes + vals0.nbytes)
    sig_fn = _jnp_sig_fn()
    with trace.span("aig_sim.launch", engine="jnp", w=2 * n_words, queries=prog.n_nodes):
        out = np.asarray(sig_fn(jnp.asarray(prog.waves), jnp.asarray(vals0)))  # repro: host-boundary
    with trace.span("aig_sim.unpack"):
        return np.ascontiguousarray(out[: prog.n_nodes]).view("<u8")


# ---------------------------------------------------------------------------
# Kernel registration (static analyzer)
# ---------------------------------------------------------------------------
# The jnp engines register representative-shape builders so
# `repro.analysis.jaxpr_lint` can abstract-trace them; the Pallas engine
# registers its counter only (tracing a pallas_call needs the TPU
# lowering machinery, and the AST layer already enforces its counter
# discipline statically).  ``x64=False``: these kernels are pure uint32
# bit algebra — there are no floats to drift.


def _ex_aig_eval():
    # plain numpy operands: jit traces them identically, and the builder
    # then holds no device arrays at all
    waves = np.zeros((2, 4, 4), dtype=np.int32)
    waves[:, :, 3] = 7  # padding instructions write the scratch row
    pin_rows = np.array([-1, 0, 1, -1, -1, -1, -1, -1], dtype=np.int32)
    elem = np.ones((2, 1), dtype=np.uint32)
    rootp = np.array([6 << 1, (5 << 1) | 1], dtype=np.int32)
    return _registry.KernelExample(
        fn=_make_jnp_mega(),
        args=(waves, pin_rows, elem, rootp),
    )


def _ex_aig_sig():
    waves = np.zeros((2, 4, 4), dtype=np.int32)
    waves[:, :, 3] = 7
    vals0 = np.zeros((8, 2), dtype=np.uint32)
    return _registry.KernelExample(
        fn=_make_jnp_sig(),
        args=(waves, vals0),
    )


_registry.register_kernel("aig_eval", __name__, _ex_aig_eval, x64=False)
_registry.register_kernel("aig_sig", __name__, _ex_aig_sig, x64=False)
_registry.register_counter("aig_eval_pallas", __name__)
