"""Tensorized back half of Algorithm I — the rapid-assessment engine.

The paper's headline claim is a *rapid assessment mechanism*: 6900+
(recipe x topology) evaluations across the EPFL suite.  The scalar path
(`mapping.schedule_stats` + `sram.evaluate` inside `explorer.explore`)
walks that grid one Python dataclass at a time; this module batches it
into a structure-of-arrays program so the whole
ChaAIG -> Evaluate -> FilterEnergy sweep is one jitted `jax.numpy` pass:

  * ``TopologyTable``  — the SRAM topology library stacked into arrays
    (rows, cols, macro counts, total bits, sense-amp widths);
  * ``WorkloadTable`` / ``SuiteTable`` — the characterized recipes of
    one circuit / a whole suite stacked into op-count tensors
    (``(R, L, 3)`` / ``(C, R, L, 3)``);
  * ``evaluate_select_suite`` — the one back-half program: schedule
    (`mapping.schedule_stats`, both the "list" and "levels"
    disciplines), evaluate (`sram.evaluate`, both "paper" and
    "physical" accounting modes) and the three-tier FilterEnergy argmin
    over circuits x model variants x topologies x recipes in one jitted
    **device-resident** pass.  The device returns a ``SelectionResult``
    (winner indices + per-winner metrics, a few KB) instead of the full
    float64 metric tensors, and the returned grid (``SuiteGrid`` for
    one `EnergyModel`, ``SuiteVariationGrid`` for a `sram.ModelTable`)
    is *lazy* (`_LazyArrays`) — its tensors stay on device until first
    access.  A single circuit is a one-circuit suite.  The variant axis
    optionally shards across devices (`_shard_variants`);
  * ``select_best`` / ``select_best_batch`` / ``select_best_worst`` —
    the host-side capacity / latency admissibility filter + energy
    argmin/argmax: the parity reference the tests compare the fused
    winners against, and the filter `mesh_explorer` and
    `explorer.best_worst` use.  ``select_best_batch`` is the batched
    form: winners for every (circuit, variant) cell in one masked
    three-tier argmin pass (non-finite energies are inadmissible in
    every tier).  ``select_best_batch_device`` is the standalone jitted
    filter for precomputed metric arrays (mesh explorer, service
    re-rank).

Parity contract: every cycle/flag quantity is exact integer arithmetic,
and the energy expressions are the *same functions* the scalar path uses
(`sram.paper_power_mw` / `sram.physical_energy_nj`), evaluated in
float64 inside `runtime.jax_env.x64`, so ``backend="jax"`` matches
``backend="python"`` to float round-off.  Grid arrays are stored
``(n_topologies, n_recipes)`` and flattened topology-major — the exact
iteration order of the scalar loops — so argmin tie-breaking also
matches.

The energy-model constants are *traced* operands (`ModelParams`, a
pytree of float64 arrays vmapped over the variant axis), not jit
statics: the jitted core recompiles only per (grid shape, n_variants,
discipline, mode).  Changing model floats never retriggers tracing, and
one compile serves circuits x recipes x topologies x model-variants.
``WorkloadTable`` pads the level axis to a multiple of 64 to keep the
number of distinct shapes (and hence compiles) small across circuits;
`trace_counts` exposes per-kernel trace counters so tests can pin the
no-recompile contract.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Mapping, NamedTuple, Sequence

import numpy as np

from repro.analysis import registry as _registry
from repro.runtime import jax_env, trace

from .aig import AigStats
from .mapping import BITS_PER_GATE, macros_per_type
from .sram import (
    OP_TYPES,
    EnergyModel,
    ModelTable,
    SramTopology,
    area_mm2_arrays,
    paper_energy_nj,
    paper_power_mw,
    physical_energy_nj,
    table2_arrays,
)

# jax is imported lazily on the first batched call: the tables and
# select_best/select_best_worst are pure numpy, and eager `import jax`
# costs ~1s that numpy-only consumers (mesh_explorer, backend="python")
# should not pay.
jax = None
jnp = None

LEVEL_PAD = 64  # pad the level axis to multiples of this to bound recompiles


def _load_jax() -> None:
    global jax, jnp
    if jnp is not None:
        return
    import jax as _jax
    import jax.numpy as _jnp

    jax_env.setup()
    jax, jnp = _jax, _jnp


# Per-kernel jit trace counters.  The counter lines inside the kernel
# bodies execute only while jax is *tracing* (never on cached dispatch),
# so a test can assert that an N-variant sweep — or a float-only model
# change — costs exactly one (or zero) compilations.  The Counter itself
# lives in the unified registry (`repro.analysis.registry`) so every
# kernel module shares one namespace and the static analyzer can verify
# the discipline; this module re-exports it under its historical name.
# repro: kernel-module
TRACE_COUNTS = _registry.TRACE_COUNTS


def trace_counts() -> dict[str, int]:
    """Snapshot of this module's per-kernel jit trace counters (the
    scope the helper has always had — other modules' kernels tracing in
    between does not perturb whole-snapshot comparisons)."""
    return _registry.trace_counts(module=__name__)


class ModelParams(NamedTuple):
    """The `EnergyModel` constants the evaluate kernels read, as float64
    arrays with a leading variant axis — the *traced* (dynamic) model
    operand.  A NamedTuple so it is a jax pytree and the `sram` mode
    helpers' ``model.<field>`` attribute reads work unchanged inside the
    kernel.

    Scalar fields are ``(V,)`` for uniform sweeps or ``(V, T)`` for
    correlated (topology-dependent) variation — per-op fields likewise
    ``(V, 3)`` or ``(V, T, 3)``: after the variant vmap each leaf is
    ``()`` / ``(T,)`` / ``(3,)`` / ``(T, 3)``, and the grid arithmetic
    (all ``(R, T)``-shaped) broadcasts either along its trailing
    topology axis — the same float ops, no new compile path."""

    f_clk_hz: np.ndarray            # (V,) or (V, T)
    e_op_marginal_fj: np.ndarray    # (V, 3) or (V, T, 3)
    p_ctrl_mw: np.ndarray           # (V,) or (V, T)
    e_macro_cycle_fj: np.ndarray    # (V,) or (V, T)
    e_col_cycle_fj: np.ndarray      # (V,) or (V, T)
    alpha_mw_per_level: np.ndarray  # (V,) or (V, T)
    pipeline_utilization: np.ndarray  # (V,) or (V, T)


def _model_params(table: ModelTable) -> ModelParams:
    return ModelParams(
        **{
            f: np.asarray(getattr(table, f), dtype=np.float64)
            for f in ModelParams._fields
        }
    )


def _as_table(model: "EnergyModel | ModelTable | None") -> tuple[ModelTable, bool]:
    """Normalize a model argument to a `ModelTable`; the bool flags
    whether the caller asked for a variant sweep (vs a single model)."""
    if isinstance(model, ModelTable):
        return model, True
    if model is None:
        model = EnergyModel()
    return ModelTable.from_models([model]), False


def _check_topo_axis(table: ModelTable, topos: "TopologyTable") -> None:
    """A correlated table's per-topology axis must match the topology
    table it is swept against (a `(V, 1)` axis broadcasts uniformly) —
    by width, and by *identity* when the table records which topologies
    its columns were generated for: a same-length but different/reordered
    topology list would silently land each column's variation on the
    wrong macro geometry."""
    if len(table) == 0:
        raise ValueError("empty ModelTable")
    t = table.n_topologies
    if t is not None and t != len(topos):
        raise ValueError(
            f"ModelTable per-topology axis has width {t}, but the sweep "
            f"covers {len(topos)} topologies"
        )
    if table.topology_names is not None:
        actual = tuple(tp.name for tp in topos.topologies)
        if table.topology_names != actual:
            raise ValueError(
                "ModelTable's per-topology columns were generated for "
                f"topologies {table.topology_names}, but the sweep covers "
                f"{actual} — regenerate the table for this topology list"
            )


def _per_topo(arr: np.ndarray) -> np.ndarray:
    """A scalar `ModelTable` field as a (V, 1)-or-(V, T) column view, so
    it broadcasts against (T,) topology arrays either way."""
    return arr[:, None] if arr.ndim == 1 else arr


# ---------------------------------------------------------------------------
# Structure-of-arrays tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopologyTable:
    """The SRAM topology library as stacked arrays (one row per topology).

    Units: ``total_bits`` in bits (capacity check is
    ``mapping.BITS_PER_GATE`` = 4 bits/gate), ``ops_per_cycle`` in
    gate-ops per macro per clock cycle (``cols/2`` sense-amp slots).
    """

    topologies: tuple[SramTopology, ...]
    rows: np.ndarray            # (T,) bitcell rows per macro
    cols: np.ndarray            # (T,) bitcell columns per macro
    n_macros: np.ndarray        # (T,)
    total_bits: np.ndarray      # (T,) capacity in bits, all macros
    ops_per_cycle: np.ndarray   # (T,) sense-amp slots per macro per cycle
    macros_per_type: np.ndarray  # (T, 3) dedicated macros per op type
    is_single: np.ndarray       # (T,) bool — time-multiplexed single macro

    @classmethod
    def from_topologies(cls, topos: Sequence[SramTopology]) -> "TopologyTable":
        """Stack topologies (library entries and/or `sram.topology_grid`
        design points) into one table; rejects unsupported macro counts."""
        topos = tuple(topos)
        if not topos:
            raise ValueError("empty topology list")
        return cls(
            topologies=topos,
            rows=np.array([t.rows for t in topos], dtype=np.int32),
            cols=np.array([t.cols for t in topos], dtype=np.int32),
            n_macros=np.array([t.n_macros for t in topos], dtype=np.int32),
            total_bits=np.array([t.total_bits for t in topos], dtype=np.int32),
            ops_per_cycle=np.array(
                [t.ops_per_cycle_per_macro for t in topos], dtype=np.int32
            ),
            macros_per_type=np.array(
                [macros_per_type(t.n_macros) for t in topos], dtype=np.int32
            ),
            is_single=np.array([t.n_macros == 1 for t in topos], dtype=bool),
        )

    def __len__(self) -> int:
        return len(self.topologies)

    def area_mm2(self, model: "EnergyModel | ModelTable") -> np.ndarray:
        """Vectorized `SramTopology.area_mm2` — the same
        `sram.area_mm2_arrays` expression over the stacked ``total_bits``:
        ``(T,)`` for one `EnergyModel`, ``(V, T)`` for a `ModelTable`
        (whose area fields may themselves be per-topology ``(V, T)``)."""
        if isinstance(model, ModelTable):
            _check_topo_axis(model, self)
            return area_mm2_arrays(
                self.total_bits[None, :],
                _per_topo(model.bitcell_um2),
                _per_topo(model.periphery_overhead),
            )
        return area_mm2_arrays(
            self.total_bits.astype(np.float64),
            model.bitcell_um2,
            model.periphery_overhead,
        )


@dataclasses.dataclass(frozen=True)
class WorkloadTable:
    """Characterized recipes as a stacked op-count tensor.

    ``ops[r, l, k]`` is the number of ops of type ``OP_TYPES[k]`` in gate-
    netlist level ``l`` of recipe ``r``; levels beyond ``n_levels[r]`` are
    zero padding (the schedule kernels mask them out).
    """

    recipes: tuple[tuple[str, ...], ...]
    ops: np.ndarray        # (R, L_pad, 3)
    n_levels: np.ndarray   # (R,)
    op_totals: np.ndarray  # (R, 3)
    gates: np.ndarray      # (R,)

    @classmethod
    def from_stats(
        cls,
        items: Mapping[tuple[str, ...], AigStats]
        | Sequence[tuple[tuple[str, ...], AigStats]],
        pad_levels_to: int = LEVEL_PAD,
    ) -> "WorkloadTable":
        if isinstance(items, Mapping):
            items = list(items.items())
        items = list(items)
        if not items:
            raise ValueError("empty workload list")
        recipes = tuple(tuple(r) for r, _ in items)
        n_levels = np.array([s.n_levels for _, s in items], dtype=np.int32)
        max_l = int(n_levels.max(initial=1))
        pad = max(pad_levels_to, 1)
        l_pad = ((max(max_l, 1) + pad - 1) // pad) * pad
        ops = np.zeros((len(items), l_pad, len(OP_TYPES)), dtype=np.int32)
        for i, (_, s) in enumerate(items):
            m = s.ops_matrix()
            ops[i, : m.shape[0]] = m
        op_totals = ops.sum(axis=1)
        return cls(
            recipes=recipes,
            ops=ops,
            n_levels=n_levels,
            op_totals=op_totals,
            gates=op_totals.sum(axis=1),
        )

    def __len__(self) -> int:
        return len(self.recipes)


@dataclasses.dataclass(frozen=True)
class SuiteTable:
    """A whole benchmark suite's `WorkloadTable`s stacked on a leading
    circuit axis — the input of the circuits x recipes x topologies sweep.

    All circuits share one recipe list (Algorithm I applies the same 64
    recipes to every RTL input) and one padded level axis (the max over
    the suite, rounded up to `LEVEL_PAD`); levels beyond ``n_levels[c, r]``
    are zero padding which the schedule kernels mask out, so padded
    results are bit-identical to each circuit's own `WorkloadTable` run.

    ``ops[c, r, l, k]``: ops of type ``OP_TYPES[k]`` in level ``l`` of
    recipe ``r`` of circuit ``c``.
    """

    circuits: tuple[str, ...]
    recipes: tuple[tuple[str, ...], ...]
    ops: np.ndarray        # (C, R, L_pad, 3)
    n_levels: np.ndarray   # (C, R)
    op_totals: np.ndarray  # (C, R, 3)
    gates: np.ndarray      # (C, R)

    @classmethod
    def from_cha(
        cls,
        cha: Mapping[str, Mapping[tuple[str, ...], AigStats]],
        pad_levels_to: int = LEVEL_PAD,
    ) -> "SuiteTable":
        """Stack per-circuit characterizations (as produced by
        `transforms.characterize_suite` / `explorer.characterize_recipes`).
        Every circuit must cover the same recipe set."""
        if not cha:
            raise ValueError("empty suite")
        names = tuple(cha)
        recipes = tuple(cha[names[0]])
        for name in names:
            if tuple(cha[name]) != recipes:
                raise ValueError(
                    f"circuit {name!r} covers a different recipe set"
                )
        max_l = max(
            (s.n_levels for m in cha.values() for s in m.values()), default=1
        )
        pad = max(pad_levels_to, 1)
        l_pad = ((max(max_l, 1) + pad - 1) // pad) * pad
        shape = (len(names), len(recipes))
        ops = np.zeros(shape + (l_pad, len(OP_TYPES)), dtype=np.int32)
        op_totals = np.zeros(shape + (len(OP_TYPES),), dtype=np.int64)
        n_levels = np.zeros(shape, dtype=np.int32)
        for c, name in enumerate(names):
            for r, s in enumerate(cha[name].values()):
                m = s.ops_matrix()
                ops[c, r, : m.shape[0]] = m
                op_totals[c, r] = m.sum(axis=0)
                n_levels[c, r] = s.n_levels
        return cls(
            circuits=names,
            recipes=recipes,
            ops=ops,
            n_levels=n_levels,
            op_totals=op_totals,
            gates=op_totals.sum(axis=2),
        )

    @classmethod
    def from_workloads(
        cls, works: Mapping[str, WorkloadTable]
    ) -> "SuiteTable":
        """Stack prebuilt workload tables, re-padding to a common level
        axis when they disagree."""
        if not works:
            raise ValueError("empty suite")
        names = tuple(works)
        recipes = works[names[0]].recipes
        for name in names:
            if works[name].recipes != recipes:
                raise ValueError(
                    f"circuit {name!r} covers a different recipe set"
                )
        l_pad = max(w.ops.shape[1] for w in works.values())
        ops = np.zeros(
            (len(names), len(recipes), l_pad, len(OP_TYPES)), dtype=np.int32
        )
        for i, name in enumerate(names):
            w = works[name].ops
            ops[i, :, : w.shape[1]] = w
        op_totals = ops.sum(axis=2)
        return cls(
            circuits=names,
            recipes=recipes,
            ops=ops,
            n_levels=np.stack([works[n].n_levels for n in names]),
            op_totals=op_totals,
            gates=op_totals.sum(axis=2),
        )

    def bucket_shape(self, n_topologies: int, n_variants: int = 1) -> tuple:
        """The jit-trace bucket this table compiles under (see
        `bucket_suite`): ``(C, R, L_pad, T, V)``.  Two suites with equal
        bucket shapes reuse one compiled `evaluate_select_suite` trace."""
        c, r, l, _ = self.ops.shape
        return (c, r, l, int(n_topologies), int(n_variants))

    def workload(self, circuit: str | int) -> WorkloadTable:
        """One circuit's rows as a standalone `WorkloadTable` view."""
        c = self.circuit_index(circuit)
        return WorkloadTable(
            recipes=self.recipes,
            ops=self.ops[c],
            n_levels=self.n_levels[c],
            op_totals=self.op_totals[c],
            gates=self.gates[c],
        )

    def circuit_index(self, circuit: str | int) -> int:
        if isinstance(circuit, int):
            return circuit
        return self.circuits.index(circuit)

    def __len__(self) -> int:
        return len(self.circuits)


# ---------------------------------------------------------------------------
# Bucket-shape helpers (continuous batching for the exploration service)
# ---------------------------------------------------------------------------
#
# The jitted suite kernels trace once per input *shape* — (C, R, L_pad)
# on the workload side, (T,) on the topology side, (V,) on the model
# side.  A long-lived service answering arbitrary circuits must therefore
# snap every batch onto a small set of canonical shapes, or each new
# request size pays a fresh multi-second compile.  The helpers below
# implement that snapping: the circuit axis pads up to a power of two
# and the (already LEVEL_PAD-quantized) level axis pads up to a
# power-of-two multiple of LEVEL_PAD, so the number of distinct traces
# grows logarithmically with the largest batch/circuit ever seen.
# Padding rows duplicate a real circuit (all cells stay finite, so the
# fused on-device selection never trips on them) and are named so
# callers can recognize and drop them.

#: Name prefix of padding rows introduced by `pad_suite`.
PAD_CIRCUIT_PREFIX = "__pad"


def ceil_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (and >= 1)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket_levels(n_levels: int, pad: int = LEVEL_PAD) -> int:
    """Canonical level-axis width for a suite whose deepest circuit has
    ``n_levels`` levels: the smallest power-of-two multiple of ``pad``
    that covers it (64, 128, 256, ... for the default `LEVEL_PAD`), so
    progressively deeper circuits step through O(log L) shapes instead
    of one shape per depth."""
    pad = max(int(pad), 1)
    return pad * ceil_pow2(_ceil_div(max(int(n_levels), 1), pad))


def pad_suite(
    suite: SuiteTable,
    n_circuits: int | None = None,
    pad_levels_to: int | None = None,
) -> SuiteTable:
    """Pad a `SuiteTable` into a canonical bucket shape.

    The circuit axis grows to ``n_circuits`` by *duplicating the first
    circuit's rows* under `PAD_CIRCUIT_PREFIX` names — real (finite)
    workloads rather than zeros, so every padded cell evaluates to
    finite metrics and the fused selection's all-non-finite guard never
    fires on padding.  The level axis grows to ``pad_levels_to`` with
    zero rows, which the schedule kernels mask out (``n_levels`` is
    unchanged) — padded results are bit-identical per real circuit.

    Defaults: ``n_circuits`` -> `ceil_pow2` of the current count,
    ``pad_levels_to`` -> `bucket_levels` of the current level width.
    """
    c, r, l, k = suite.ops.shape
    n_c = ceil_pow2(c) if n_circuits is None else int(n_circuits)
    l_pad = bucket_levels(l) if pad_levels_to is None else int(pad_levels_to)
    if n_c < c:
        raise ValueError(f"cannot pad {c} circuits down to {n_c}")
    if l_pad < l:
        raise ValueError(f"cannot pad level axis {l} down to {l_pad}")
    if n_c == c and l_pad == l:
        return suite
    names = list(suite.circuits)
    for i in range(n_c - c):
        names.append(f"{PAD_CIRCUIT_PREFIX}{i}")
    ops = np.zeros((n_c, r, l_pad, k), dtype=suite.ops.dtype)
    ops[:c, :, :l] = suite.ops
    ops[c:, :, :l] = suite.ops[0]
    n_levels = np.concatenate(
        [suite.n_levels, np.broadcast_to(suite.n_levels[0], (n_c - c, r))]
    )
    op_totals = ops.sum(axis=2)
    return SuiteTable(
        circuits=tuple(names),
        recipes=suite.recipes,
        ops=ops,
        n_levels=n_levels,
        op_totals=op_totals,
        gates=op_totals.sum(axis=2),
    )


def bucket_suite(
    suite: SuiteTable, n_topologies: int, n_variants: int = 1
) -> "tuple[SuiteTable, tuple]":
    """Snap a suite onto its canonical bucket: `pad_suite` with the
    default (power-of-two) targets, returning the padded table and its
    `SuiteTable.bucket_shape` key ``(C, R, L_pad, T, V)`` — the unit of
    jit-trace reuse for the exploration service."""
    padded = pad_suite(suite)
    return padded, padded.bucket_shape(n_topologies, n_variants)


# ---------------------------------------------------------------------------
# Schedule + evaluate math, traced inside the fused program
# ---------------------------------------------------------------------------


def _ceil_div(a, b):
    return -(-a // b)


def _schedule_core(ops, n_levels, width, mpt, is_single, total_bits, rows,
                   discipline):
    """Shared schedule math; mirrors mapping.schedule_stats exactly.

    Shapes: ops (R, L, 3); width (T,); mpt (T, 3); is_single (T,);
    total_bits (T,); rows (T,).  Returns (cycles, active_macro_cycles,
    fits), each (R, T) with integer dtype (bool for fits).
    """
    wt = width[None, :, None] * mpt[None, :, :]          # (1, T, 3)
    tot = ops.sum(axis=1)                                # (R, 3)
    gates = tot.sum(axis=-1)                             # (R,)

    if discipline == "list":
        # ASAP width-bound schedule: cycles = max(depth, width bound) + drain.
        b = _ceil_div(tot[:, None, :], wt)               # (R, T, 3)
        sum_b = b.sum(axis=-1)
        max_b = b.max(axis=-1)
        width_bound = jnp.where(is_single[None, :], sum_b, max_b)
        active = jnp.where(
            is_single[None, :], sum_b, (b * mpt[None, :, :]).sum(axis=-1)
        )
        cycles = jnp.maximum(n_levels[:, None], width_bound) + 1
        # Steady-state working set: ~width_bound/depth concurrent batches,
        # each needing 2 operand rows + 1 result row.
        rows_needed = 3 * _ceil_div(
            jnp.maximum(width_bound, 1), jnp.maximum(n_levels[:, None], 1)
        ) + 2
    elif discipline == "levels":
        # Lock-step: every real level pays max(1, per-type batch bound);
        # the single-macro case serializes the three op types.
        b = _ceil_div(ops[:, None, :, :], wt[:, :, None, :])   # (R, T, L, 3)
        real = jnp.arange(ops.shape[1])[None, :] < n_levels[:, None]  # (R, L)
        sum_b = b.sum(axis=-1)                           # (R, T, L)
        max_b = b.max(axis=-1)
        per_level = jnp.where(
            is_single[None, :, None],
            jnp.maximum(sum_b, 1),
            jnp.maximum(max_b, 1),
        )
        per_level = per_level * real[:, None, :]
        cycles = per_level.sum(axis=-1) + 1              # + pipeline drain
        active = jnp.where(
            is_single[None, :],
            b.sum(axis=(-1, -2)),
            (b * mpt[None, :, None, :]).sum(axis=(-1, -2)),
        )
        # The busiest level's batch schedule is the peak working set.
        rows_needed = 3 * per_level.max(axis=-1) + 2     # (R, T)
    else:
        raise ValueError(f"unknown discipline {discipline!r}")

    # Feasibility = bit capacity (Alg. I line 9) AND row budget — the
    # same two-term check as mapping.schedule_stats / _schedule_list.
    fits = (BITS_PER_GATE * gates[:, None] <= total_bits[None, :]) & (
        rows_needed <= rows[None, :]
    )
    return cycles, active, fits


def _evaluate_core(ops, n_levels, width, mpt, is_single, total_bits, rows,
                   cols, params, discipline, mode):
    """Schedule once, then evaluate every model variant over it.

    ``params`` is a `ModelParams` pytree of *traced* float64 arrays with a
    leading variant axis; the schedule (exact integers, model-free) is
    computed once and closed over by the vmapped per-variant metrics, so
    the variant axis only multiplies the cheap float arithmetic.

    Returns ``cycles`` / ``active_macro_cycles`` / ``fits`` as (R, T)
    arrays and each metric as a (V, R, T) array.
    """
    cycles, active, fits = _schedule_core(
        ops, n_levels, width, mpt, is_single, total_bits, rows, discipline
    )
    tot = ops.sum(axis=1)                                # (R, 3)
    gates = tot.sum(axis=-1)                             # (R,)
    n_lvl = n_levels.astype(jnp.float64)[:, None]
    # Explicit float64 casts so parity with the scalar path does not
    # hinge on int/weak-float promotion rules.
    cycles_f = cycles.astype(jnp.float64)

    def metrics(model):
        # `model` is one ModelParams row: scalar or (T,) leaves + a (3,)
        # or (T, 3) per-op vector.  The sram mode helpers read it via the
        # same attribute names as a scalar EnergyModel, so both paths
        # share one set of expressions.
        t_ns = cycles_f / model.f_clk_hz * 1e9
        e_marg = model.e_op_marginal_fj
        if e_marg.ndim == 2:  # (T, 3) correlated per-op energies
            e_ops_fj = (tot[:, None, :] * e_marg[None, :, :]).sum(axis=-1)
        else:
            e_ops_fj = (tot * e_marg[None, :]).sum(axis=-1)

        if mode == "paper":
            p_mw = paper_power_mw(n_lvl, model) * jnp.ones_like(t_ns)
            e_nj = paper_energy_nj(p_mw, t_ns)
        elif mode == "physical":
            e_nj = physical_energy_nj(
                t_ns, active,
                e_ops_fj if e_ops_fj.ndim == 2 else e_ops_fj[:, None],
                cols[None, :], model,
            )
            p_mw = jnp.where(t_ns > 0, e_nj / t_ns * 1e3, 0.0)
        else:
            raise ValueError(f"unknown mode {mode!r}")

        thr_gops = jnp.where(
            t_ns > 0,
            gates[:, None] / (t_ns * 1e-9) / 1e9 * model.pipeline_utilization,
            0.0,
        )
        tops_w = jnp.where(p_mw > 0, (thr_gops / 1e3) / (p_mw * 1e-3), 0.0)
        return dict(
            latency_ns=t_ns,
            energy_nj=e_nj,
            power_mw=p_mw,
            throughput_gops=thr_gops,
            tops_per_watt=tops_w,
        )

    out = jax.vmap(metrics)(params)                      # each (V, R, T)
    out.update(cycles=cycles, active_macro_cycles=active, fits=fits)
    return out


# ---------------------------------------------------------------------------
# Sweep grids
# ---------------------------------------------------------------------------


_SCHED_KEYS = ("cycles", "active_macro_cycles", "fits")
_METRIC_KEYS = (
    "latency_ns", "energy_nj", "power_mw", "throughput_gops", "tops_per_watt"
)
# Grid fields that hold device-resident (jax) arrays until first read.
_LAZY_FIELDS = frozenset(_SCHED_KEYS + _METRIC_KEYS)

# Deferred views made by the grids' view methods, and deferred slices
# resolved on the device (`_Slice.resolve`): the explorer's assembly span
# reports both, so a view that is made and never read shows as a view
# without a slice.
VIEW_COUNTS = {"views": 0, "slices": 0}


def _compose(index: tuple, idx: tuple) -> tuple:
    """``idx`` applied to ``root[index]``, as one index into ``root``.

    Both hold integers and full slices only: each full slice of
    ``index`` takes the next entry of ``idx``, the rest of ``idx`` goes
    on the trailing axes."""
    rest = iter(idx)
    out = tuple(next(rest, i) if isinstance(i, slice) else i for i in index)
    return out + tuple(rest)


class _Slice:
    """``root[index]`` of a device array, not yet dispatched: what a lazy
    grid's view holds in place of a device slice.  A view of a view
    composes the index, so the root is always the array the device
    program returned."""

    __slots__ = ("root", "index")

    def __init__(self, root, index: tuple):
        self.root, self.index = root, index

    @property
    def shape(self) -> tuple:
        kept = (n for n, i in zip(self.root.shape, self.index)
                if isinstance(i, slice))
        return tuple(kept) + tuple(self.root.shape[len(self.index):])

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def resolve(self):
        """The slice as a device array: one device op."""
        VIEW_COUNTS["slices"] += 1
        return self.root[self.index]


def _view(a, idx: tuple):
    """``a[idx]`` without device work: a numpy view of a numpy array, a
    deferred `_Slice` of a device array or of a `_Slice`."""
    if isinstance(a, np.ndarray):
        return a[idx]
    if not idx:
        return a
    if isinstance(a, _Slice):
        return _Slice(a.root, _compose(a.index, idx))
    return _Slice(a, idx)


class _LazyArrays:
    """Mixin for the grid dataclasses: metric/schedule fields hold
    *device* (jax) arrays, as the fused program returned them, until
    they are read.  A field is materialized to numpy on first attribute
    access and cached in place (the dataclasses are frozen, so the swap
    goes through ``object.__setattr__``), which means a grid that is never
    inspected never pays the device->host transfer: the fused selection
    already moved the winners across, and the full (C, V, T, R) tensors
    stay where they were computed.

    View methods (``grid``/``variation``/``suite``) hand their child
    grids deferred slices (`_Slice`) of the device arrays, so making a
    view does no device work either: a child's field is sliced on the
    device when it is first read, through ``_raw`` or an attribute, and
    views of materialized (numpy) fields are plain numpy views.
    """

    def __getattribute__(self, name):
        val = object.__getattribute__(self, name)
        if name in _LAZY_FIELDS and not isinstance(val, np.ndarray):
            # repro: host-boundary — lazy-grid materialization on first access
            val = np.asarray(self._raw(name))
            object.__setattr__(self, name, val)
        return val

    def _stored(self, name: str):
        """The stored field as it is: numpy, device array or `_Slice`."""
        return object.__getattribute__(self, name)

    def _raw(self, name: str):
        """The stored array without materializing it (device or numpy);
        a deferred slice is dispatched here, once, and cached in place."""
        val = object.__getattribute__(self, name)
        if isinstance(val, _Slice):
            val = val.resolve()
            object.__setattr__(self, name, val)
        return val

    def _views(self, sched_idx: tuple, metric_idx: tuple) -> dict:
        """The schedule and metric fields of a view: each stored field
        indexed by ``sched_idx`` / ``metric_idx`` through `_view`."""
        out = {k: _view(self._stored(k), sched_idx) for k in _SCHED_KEYS}
        out.update({k: _view(self._stored(k), metric_idx) for k in _METRIC_KEYS})
        if any(isinstance(a, _Slice) for a in out.values()):
            VIEW_COUNTS["views"] += 1
        return out

    def _cell_scalar(self, name: str, idx: tuple) -> float:
        """One element of a (possibly device-resident) field.

        Indexing the raw array (a deferred slice's root, at the composed
        index) keeps the gather on the device and moves a single scalar
        across the boundary — the field is NOT materialized (and stays
        lazy for later accesses).
        """
        val = self._stored(name)
        if isinstance(val, _Slice):
            val, idx = val.root, _compose(val.index, idx)
        # repro: host-boundary — single-scalar device gather
        return float(np.asarray(val[idx]))


@dataclasses.dataclass(frozen=True)
class GridCell:
    """One design point of a sweep grid — the lazy per-cell gather result.

    Produced by the grids' ``cell(...)`` methods for post-hoc inspection
    of a single (circuit, variant, topology, recipe) choice without
    materializing the full device tensor: each field is a one-element
    device gather.  ``circuit``/``variant`` are None on grids without
    that axis.
    """

    recipe: tuple[str, ...]
    topology: SramTopology
    circuit: str | None
    variant: int | None
    cycles: int
    active_macro_cycles: int
    fits: bool
    feasible: bool
    latency_ns: float
    energy_nj: float
    power_mw: float
    throughput_gops: float
    tops_per_watt: float
    area_mm2: float


@dataclasses.dataclass(frozen=True)
class ExplorationGrid(_LazyArrays):
    """The full recipe x topology sweep as ``(n_topologies, n_recipes)``
    arrays — the batched analogue of ``ExplorationResult.evaluations``.

    Flattened (``.ravel()``) order is topology-major, matching the scalar
    loops ``for topo: for recipe:`` so argmin indices and tie-breaking
    line up with the Python path.
    """

    recipes: tuple[tuple[str, ...], ...]
    topologies: tuple[SramTopology, ...]
    cycles: np.ndarray               # (T, R) int
    active_macro_cycles: np.ndarray  # (T, R) int
    fits: np.ndarray                 # (T, R) bool
    latency_ns: np.ndarray           # (T, R)
    energy_nj: np.ndarray            # (T, R)
    power_mw: np.ndarray             # (T, R)
    throughput_gops: np.ndarray      # (T, R)
    tops_per_watt: np.ndarray        # (T, R)
    area_mm2: np.ndarray             # (T,)
    feasible: np.ndarray             # (T,) capacity-feasible (Alg. I line 9)
    mode: str
    discipline: str
    # The scalar model the grid was evaluated with; None when the grid is
    # a correlated-variant slice whose constants differ per topology (no
    # single EnergyModel exists — see ModelTable.uniform_row).
    model: EnergyModel | None

    @property
    def size(self) -> int:
        # _stored: a shape query must not slice or materialize a lazy field
        return self._stored("energy_nj").size

    def unravel(self, flat_index: int) -> tuple[int, int]:
        """Flat (topology-major) index -> (topology_idx, recipe_idx)."""
        n_r = len(self.recipes)
        return flat_index // n_r, flat_index % n_r

    def fit_energies(self) -> np.ndarray:
        return self.energy_nj[self.fits]

    def best_index(self, max_latency_ns: float | None = None) -> int:
        return select_best(
            self.energy_nj,
            self.fits,
            latency=self.latency_ns,
            max_latency=max_latency_ns,
            feasible=np.broadcast_to(self.feasible[:, None], self.fits.shape),
        )

    def best_worst_indices(self) -> tuple[int, int]:
        return select_best_worst(self.energy_nj, self.fits)

    def cell(self, t: int, r: int) -> GridCell:
        """One (topology, recipe) design point as a `GridCell` — lazy
        per-element gathers, never materializes the full grid."""
        g = self._cell_scalar
        return GridCell(
            recipe=self.recipes[r],
            topology=self.topologies[t],
            circuit=None,
            variant=None,
            cycles=int(g("cycles", (t, r))),
            active_macro_cycles=int(g("active_macro_cycles", (t, r))),
            fits=bool(g("fits", (t, r))),
            feasible=bool(np.asarray(self._raw("feasible")[t])),  # repro: host-boundary
            latency_ns=g("latency_ns", (t, r)),
            energy_nj=g("energy_nj", (t, r)),
            power_mw=g("power_mw", (t, r)),
            throughput_gops=g("throughput_gops", (t, r)),
            tops_per_watt=g("tops_per_watt", (t, r)),
            area_mm2=float(np.asarray(self._raw("area_mm2")[t])),  # repro: host-boundary
        )


@dataclasses.dataclass(frozen=True)
class VariationGrid(_LazyArrays):
    """One circuit's recipe x topology sweep across every `ModelTable`
    variant — the batched analogue of N `ExplorationGrid`s that cost one
    compile and one device call.

    Schedules (``cycles`` / ``active_macro_cycles`` / ``fits``) are
    model-free exact integers, stored once as ``(T, R)``; each metric
    carries a leading variant axis ``(V, T, R)``.  ``grid(v)`` slices
    variant ``v`` back out as a standard `ExplorationGrid` (deferred
    device slices of a lazy grid, numpy views once materialized — see
    `_LazyArrays`).
    """

    recipes: tuple[tuple[str, ...], ...]
    topologies: tuple[SramTopology, ...]
    models: ModelTable
    cycles: np.ndarray               # (T, R) int
    active_macro_cycles: np.ndarray  # (T, R) int
    fits: np.ndarray                 # (T, R) bool
    latency_ns: np.ndarray           # (V, T, R)
    energy_nj: np.ndarray            # (V, T, R)
    power_mw: np.ndarray             # (V, T, R)
    throughput_gops: np.ndarray      # (V, T, R)
    tops_per_watt: np.ndarray        # (V, T, R)
    area_mm2: np.ndarray             # (V, T)
    feasible: np.ndarray             # (T,)
    mode: str
    discipline: str

    @property
    def n_variants(self) -> int:
        return len(self.models)

    def __len__(self) -> int:
        return len(self.models)

    def unravel(self, flat_index: int) -> tuple[int, int]:
        """Flat (topology-major) index -> (topology_idx, recipe_idx)."""
        n_r = len(self.recipes)
        return flat_index // n_r, flat_index % n_r

    def grid(self, v: int) -> ExplorationGrid:
        """Variant ``v``'s sweep as a standard `ExplorationGrid`.

        For a correlated table, a topology-dependent variant has no
        single scalar model: the slice still carries every per-variant
        metric (winners, energies, areas all work), but its ``model``
        field is None — materialize per-cell models via
        ``models.model(v, topology=...)`` instead."""
        return ExplorationGrid(
            recipes=self.recipes,
            topologies=self.topologies,
            **self._views((), (v,)),
            area_mm2=self.area_mm2[v],
            feasible=self.feasible,
            mode=self.mode,
            discipline=self.discipline,
            model=(
                self.models.model(v) if self.models.uniform_row(v) else None
            ),
        )

    def best_indices(self, max_latency_ns: float | None = None) -> np.ndarray:
        """Per-variant `select_best` winners: ``(V,)`` flat
        (topology-major) indices, same tiering/tie-breaking as the
        static-model path on every variant — all variants in one
        `select_best_batch` array pass (the model-free fits/feasible
        masks broadcast across the variant axis)."""
        v = len(self.models)
        feas = np.broadcast_to(self.feasible[:, None], self.fits.shape)
        return select_best_batch(
            self.energy_nj.reshape(v, -1),
            self.fits.reshape(1, -1),
            latency=self.latency_ns.reshape(v, -1),
            max_latency=max_latency_ns,
            feasible=feas.reshape(1, -1),
        )

    def cell(self, v: int, t: int, r: int) -> GridCell:
        """One (variant, topology, recipe) design point as a `GridCell`
        — lazy per-element gathers, never materializes the full
        ``(V, T, R)`` tensors."""
        g = self._cell_scalar
        return GridCell(
            recipe=self.recipes[r],
            topology=self.topologies[t],
            circuit=None,
            variant=v,
            cycles=int(g("cycles", (t, r))),
            active_macro_cycles=int(g("active_macro_cycles", (t, r))),
            fits=bool(g("fits", (t, r))),
            feasible=bool(np.asarray(self._raw("feasible")[t])),  # repro: host-boundary
            latency_ns=g("latency_ns", (v, t, r)),
            energy_nj=g("energy_nj", (v, t, r)),
            power_mw=g("power_mw", (v, t, r)),
            throughput_gops=g("throughput_gops", (v, t, r)),
            tops_per_watt=g("tops_per_watt", (v, t, r)),
            area_mm2=float(np.asarray(self._raw("area_mm2")[v, t])),  # repro: host-boundary
        )


@dataclasses.dataclass(frozen=True)
class SuiteGrid(_LazyArrays):
    """The whole-suite sweep as ``(n_circuits, n_topologies, n_recipes)``
    arrays — one `ExplorationGrid` per circuit, stacked.

    Produced by `evaluate_select_suite`; ``grid(circuit)`` slices one
    circuit back out as a standard `ExplorationGrid` (deferred device
    slices of a lazy grid, numpy views once materialized), so
    everything downstream of the per-circuit sweep (``best_index``,
    `select_best`, `explorer.best_worst`) works unchanged.
    """

    circuits: tuple[str, ...]
    recipes: tuple[tuple[str, ...], ...]
    topologies: tuple[SramTopology, ...]
    cycles: np.ndarray               # (C, T, R) int
    active_macro_cycles: np.ndarray  # (C, T, R) int
    fits: np.ndarray                 # (C, T, R) bool
    latency_ns: np.ndarray           # (C, T, R)
    energy_nj: np.ndarray            # (C, T, R)
    power_mw: np.ndarray             # (C, T, R)
    throughput_gops: np.ndarray      # (C, T, R)
    tops_per_watt: np.ndarray        # (C, T, R)
    area_mm2: np.ndarray             # (T,)
    feasible: np.ndarray             # (C, T) capacity-feasible per circuit
    mode: str
    discipline: str
    model: EnergyModel | None  # None for correlated-variant slices

    @property
    def size(self) -> int:
        """Total swept implementations (circuits x topologies x recipes)."""
        return self._stored("energy_nj").size

    def circuit_index(self, circuit: str | int) -> int:
        if isinstance(circuit, int):
            return circuit
        return self.circuits.index(circuit)

    def grid(self, circuit: str | int) -> ExplorationGrid:
        """One circuit's ``(T, R)`` slice as an `ExplorationGrid`."""
        c = self.circuit_index(circuit)
        return ExplorationGrid(
            recipes=self.recipes,
            topologies=self.topologies,
            **self._views((c,), (c,)),
            area_mm2=self.area_mm2,
            feasible=self.feasible[c],
            mode=self.mode,
            discipline=self.discipline,
            model=self.model,
        )

    def grids(self) -> dict[str, ExplorationGrid]:
        return {name: self.grid(name) for name in self.circuits}

    def cell(self, circuit: str | int, t: int, r: int) -> GridCell:
        """One (circuit, topology, recipe) design point as a `GridCell`
        — lazy per-element gathers, never materializes the full
        ``(C, T, R)`` tensors."""
        c = self.circuit_index(circuit)
        g = self._cell_scalar
        return GridCell(
            recipe=self.recipes[r],
            topology=self.topologies[t],
            circuit=self.circuits[c],
            variant=None,
            cycles=int(g("cycles", (c, t, r))),
            active_macro_cycles=int(g("active_macro_cycles", (c, t, r))),
            fits=bool(g("fits", (c, t, r))),
            feasible=bool(np.asarray(self._raw("feasible")[c, t])),  # repro: host-boundary
            latency_ns=g("latency_ns", (c, t, r)),
            energy_nj=g("energy_nj", (c, t, r)),
            power_mw=g("power_mw", (c, t, r)),
            throughput_gops=g("throughput_gops", (c, t, r)),
            tops_per_watt=g("tops_per_watt", (c, t, r)),
            area_mm2=float(np.asarray(self._raw("area_mm2")[t])),  # repro: host-boundary
        )


@dataclasses.dataclass(frozen=True)
class SuiteVariationGrid(_LazyArrays):
    """The whole suite swept across every model variant: circuits x
    model-variants x topologies x recipes from ONE compile and ONE device
    call — the fourth (variant) axis of the rapid-assessment engine.

    Schedules are model-free ``(C, T, R)`` exact integers; metrics are
    ``(C, V, T, R)``.  ``variation(circuit)`` slices one circuit's
    `VariationGrid`; ``suite(v)`` slices one variant's `SuiteGrid`.
    """

    circuits: tuple[str, ...]
    recipes: tuple[tuple[str, ...], ...]
    topologies: tuple[SramTopology, ...]
    models: ModelTable
    cycles: np.ndarray               # (C, T, R) int
    active_macro_cycles: np.ndarray  # (C, T, R) int
    fits: np.ndarray                 # (C, T, R) bool
    latency_ns: np.ndarray           # (C, V, T, R)
    energy_nj: np.ndarray            # (C, V, T, R)
    power_mw: np.ndarray             # (C, V, T, R)
    throughput_gops: np.ndarray      # (C, V, T, R)
    tops_per_watt: np.ndarray        # (C, V, T, R)
    area_mm2: np.ndarray             # (V, T)
    feasible: np.ndarray             # (C, T)
    mode: str
    discipline: str

    @property
    def n_variants(self) -> int:
        return len(self.models)

    @property
    def size(self) -> int:
        """Total swept implementations (C x V x T x R)."""
        return self._stored("energy_nj").size

    def circuit_index(self, circuit: str | int) -> int:
        if isinstance(circuit, int):
            return circuit
        return self.circuits.index(circuit)

    def variation(self, circuit: str | int) -> VariationGrid:
        """One circuit's ``(V, T, R)`` sweep as a `VariationGrid`."""
        c = self.circuit_index(circuit)
        return VariationGrid(
            recipes=self.recipes,
            topologies=self.topologies,
            models=self.models,
            **self._views((c,), (c,)),
            area_mm2=self.area_mm2,
            feasible=self.feasible[c],
            mode=self.mode,
            discipline=self.discipline,
        )

    def suite(self, v: int) -> SuiteGrid:
        """One model variant's suite sweep as a standard `SuiteGrid`
        (``model`` is None for a topology-dependent correlated variant —
        see `VariationGrid.grid`)."""
        return SuiteGrid(
            circuits=self.circuits,
            recipes=self.recipes,
            topologies=self.topologies,
            **self._views((), (slice(None), v)),
            area_mm2=self.area_mm2[v],
            feasible=self.feasible,
            mode=self.mode,
            discipline=self.discipline,
            model=(
                self.models.model(v) if self.models.uniform_row(v) else None
            ),
        )

    def best_indices(self, max_latency_ns: float | None = None) -> np.ndarray:
        """Winners for every (circuit, variant) cell — ``(C, V)`` flat
        (topology-major) indices from ONE `select_best_batch` pass over
        the whole hypercube, bit-identical to running the per-variant
        `select_best` loop on each circuit's `VariationGrid`."""
        c, v = len(self.circuits), len(self.models)
        feas = np.broadcast_to(
            self.feasible[:, :, None], self.fits.shape
        )  # (C, T, R)
        return select_best_batch(
            self.energy_nj.reshape(c, v, -1),
            self.fits.reshape(c, 1, -1),
            latency=self.latency_ns.reshape(c, v, -1),
            max_latency=max_latency_ns,
            feasible=feas.reshape(c, 1, -1),
        )

    def cell(self, circuit: str | int, v: int, t: int, r: int) -> GridCell:
        """One (circuit, variant, topology, recipe) point of the full
        hypercube as a `GridCell` — lazy per-element gathers, never
        materializes the ``(C, V, T, R)`` tensors."""
        c = self.circuit_index(circuit)
        g = self._cell_scalar
        return GridCell(
            recipe=self.recipes[r],
            topology=self.topologies[t],
            circuit=self.circuits[c],
            variant=v,
            cycles=int(g("cycles", (c, t, r))),
            active_macro_cycles=int(g("active_macro_cycles", (c, t, r))),
            fits=bool(g("fits", (c, t, r))),
            feasible=bool(np.asarray(self._raw("feasible")[c, t])),  # repro: host-boundary
            latency_ns=g("latency_ns", (c, v, t, r)),
            energy_nj=g("energy_nj", (c, v, t, r)),
            power_mw=g("power_mw", (c, v, t, r)),
            throughput_gops=g("throughput_gops", (c, v, t, r)),
            tops_per_watt=g("tops_per_watt", (c, v, t, r)),
            area_mm2=float(np.asarray(self._raw("area_mm2")[v, t])),  # repro: host-boundary
        )


def _suite_feasible(suite, topos, feasible) -> np.ndarray:
    if feasible is None:
        feasible = np.ones((len(suite), len(topos)), dtype=bool)
    feasible = np.asarray(feasible, dtype=bool)
    if feasible.shape != (len(suite), len(topos)):
        raise ValueError(
            f"feasible must be (n_circuits, n_topologies)="
            f"{(len(suite), len(topos))}, got {feasible.shape}"
        )
    return feasible


def _build_suite_grid(
    suite, topos, table, model, is_sweep, mode, discipline, feasible,
    sched, mets,
) -> "SuiteGrid | SuiteVariationGrid":
    """Assemble the suite grid result from (possibly device-resident)
    schedule/metric arrays."""
    if not is_sweep:
        return SuiteGrid(
            circuits=suite.circuits,
            recipes=suite.recipes,
            topologies=topos.topologies,
            area_mm2=topos.area_mm2(table.model(0)),
            feasible=feasible,
            mode=mode,
            discipline=discipline,
            model=model if isinstance(model, EnergyModel) else table.model(0),
            **sched,
            **{k: _view(v, (slice(None), 0)) for k, v in mets.items()},
        )
    return SuiteVariationGrid(
        circuits=suite.circuits,
        recipes=suite.recipes,
        topologies=topos.topologies,
        models=table,
        area_mm2=topos.area_mm2(table),
        feasible=feasible,
        mode=mode,
        discipline=discipline,
        **sched,
        **mets,
    )


# ---------------------------------------------------------------------------
# Device-resident pipeline: fused evaluate + select, variant sharding
# ---------------------------------------------------------------------------
#
# The host-side `select_best_batch` below needs the full (C, V, T, R)
# metric tensors on the host to reduce them to (C, V) winner indices —
# for a large Monte-Carlo sweep the dominant cost would be the
# device->host transfer of data that is immediately thrown away.  The
# fused program runs the same three-tier masked argmin *inside* the
# jitted evaluate pass, so only the winners + per-winner metrics cross
# the host boundary; the full tensors stay device-resident and back the
# lazy grids.  `select_best_batch` remains the parity reference the
# tests check the fused winners against.


def _select_core(energy, fits, feasible, latency, max_latency, use_latency):
    """`select_best_batch`'s three-tier masking as pure jnp ops.

    ``energy``/``latency`` are ``(..., V, N)``; ``fits``/``feasible``
    are model-free ``(..., 1, N)`` masks broadcast across the variant
    axis.  ``use_latency`` is a trace-time static (presence of the
    latency tier changes the graph); ``max_latency`` itself is traced so
    changing the bound never recompiles.  Returns per-cell winner
    indices and a per-cell any-finite flag (the all-non-finite error is
    raised host-side — the flag is part of the small payload).
    """
    finite = jnp.isfinite(energy)
    tier2 = fits & finite
    tier1 = tier2 & feasible
    if use_latency:
        tier1 = tier1 & (latency <= max_latency)
    idx = _masked_tier_argmin(energy, (tier1, tier2, finite), xp=jnp)
    return idx, finite.any(axis=-1)


def _fused_tail(out, feasible, max_latency, use_latency):
    """Select + gather appended to the evaluate kernels, rank-generic:
    ``out`` metrics are ``(V, R, T)`` (single circuit) or ``(C, V, R, T)``
    (suite); ``feasible`` is ``(T,)`` / ``(C, T)``.

    Returns the final-layout schedule/metric tensors (these stay on
    device for the lazy grids) plus the small selection payload: winner
    indices, per-winner metrics, each variant's latency and the capacity
    flag at the *nominal* (variant-0) winner cell — everything the yield
    summary needs without touching the full tensors.
    """
    sched = {k: jnp.swapaxes(out[k], -1, -2) for k in _SCHED_KEYS}
    mets = {k: jnp.swapaxes(out[k], -1, -2) for k in _METRIC_KEYS}
    fits = sched["fits"]                              # (..., T, R)
    n = fits.shape[-2] * fits.shape[-1]

    def flat(m):  # (..., T, R) -> (..., T*R), flat topology-major
        return m.reshape(m.shape[:-2] + (n,))

    energy, latency = flat(mets["energy_nj"]), flat(mets["latency_ns"])
    fits_f = flat(fits)[..., None, :]                 # (..., 1, N)
    feas = jnp.broadcast_to(feasible[..., :, None], fits.shape)
    feas_f = flat(feas)[..., None, :]
    idx, has_finite = _select_core(
        energy, fits_f, feas_f, latency, max_latency, use_latency
    )                                                 # (..., V)

    def take(m):  # metric value at each cell's winner
        return jnp.take_along_axis(flat(m), idx[..., None], axis=-1)[..., 0]

    winner_mets = {k: take(mets[k]) for k in _METRIC_KEYS}
    # Each variant's latency / the capacity flag at the variant-0 winner.
    idx0 = idx[..., :1]
    nominal_latency = jnp.take_along_axis(
        latency, jnp.broadcast_to(idx0[..., None], idx.shape + (1,)), axis=-1
    )[..., 0]
    nominal_fits = jnp.take_along_axis(flat(fits), idx0, axis=-1)[..., 0]
    return dict(
        sched=sched,
        mets=mets,
        winner_idx=idx.astype(jnp.int32),
        has_finite=has_finite,
        winner_mets=winner_mets,
        nominal_latency=nominal_latency,
        nominal_fits=nominal_fits,
    )


def _jit_fused(fn):
    # No donation: no output has the shape of a model operand, so XLA
    # cannot alias them (on a TPU jax warns of every unusable donation).
    return jax.jit(fn, static_argnames=("discipline", "mode", "use_latency"))


def _make_fused_suite():
    def fn(ops, n_levels, width, mpt, is_single, total_bits, rows, cols,
           params, feasible, max_latency, discipline, mode, use_latency):
        TRACE_COUNTS["fused_suite"] += 1

        def per_circuit(o, nl):
            return _evaluate_core(
                o, nl, width, mpt, is_single, total_bits, rows, cols,
                params, discipline, mode,
            )

        out = jax.vmap(per_circuit)(ops, n_levels)
        return _fused_tail(out, feasible, max_latency, use_latency)

    return _jit_fused(fn)


_FUSED_SUITE = None


def _fused_program():
    global _FUSED_SUITE
    _load_jax()
    if _FUSED_SUITE is None:
        _FUSED_SUITE = _make_fused_suite()
    return _FUSED_SUITE


def _fused_outputs(res):
    """The fused program's schedule/metric dicts (already final-layout),
    left on the device for the lazy grids."""
    return (
        {k: res["sched"][k] for k in _SCHED_KEYS},
        {k: res["mets"][k] for k in _METRIC_KEYS},
    )


def _host_nbytes(operands) -> int:
    """Bytes of the host (numpy) leaves of ``operands``: what a device
    call on them copies to the device."""
    return sum(
        x.nbytes for x in jax.tree_util.tree_leaves(operands)
        if isinstance(x, (np.ndarray, np.generic))
    )


def _shard_variants(
    params: ModelParams, shard: "bool | None"
) -> tuple[ModelParams, bool]:
    """Lay the per-variant model operands out across the available
    devices.  The variant axis is embarrassingly parallel (each variant
    reads the same schedule), so a `NamedSharding` over the leading axis
    of every `ModelParams` leaf is enough for XLA's GSPMD partitioner to
    shard the whole fused evaluate+select kernel along it.

    ``shard=None`` (auto): shard when more than one device is visible
    and the variant count divides evenly; ``False``: never; ``True``:
    force a mesh even on one device (a 1-device mesh is bit-identical to
    the unsharded path — the sharded-equals-unsharded contract the tests
    pin).  Indivisible variant counts fall back to fewer devices (worst
    case 1) rather than padding, keeping results exact.
    """
    if shard is False:
        return params, False
    devs = jax.devices()
    n = len(devs)
    if shard is None and n == 1:
        return params, False
    v = int(np.shape(params.f_clk_hz)[0])
    while n > 1 and v % n:
        n -= 1
    if shard is None and n == 1:
        return params, False
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devs[:n]), ("variants",))  # repro: host-boundary
    spec = NamedSharding(mesh, PartitionSpec("variants"))
    return jax.device_put(params, spec), True


@dataclasses.dataclass(frozen=True)
class SelectionResult:
    """What the fused pipeline brings back across the host boundary: the
    winners of every (circuit, variant) cell plus their metrics — a few
    KB where the host-side filter transferred the full float64
    (C, V, T, R) tensors.

    ``winner_idx`` holds flat topology-major indices (``grid.unravel``
    decodes them), shaped ``(C, V)`` (V=1 when a single `EnergyModel`
    was evaluated).  ``nominal_latency_ns`` / ``nominal_fits`` are each
    variant's latency / the capacity flag at the *nominal* (variant-0)
    winner cell — the inputs of the latency-yield figure.
    ``payload_bytes`` is the actual number of bytes materialized to
    host for this result.
    """

    winner_idx: np.ndarray            # (V,) or (C, V) int32
    winner_metrics: dict[str, np.ndarray]  # each (V,) or (C, V) float64
    nominal_latency_ns: np.ndarray    # (V,) or (C, V)
    nominal_fits: np.ndarray          # () or (C,) bool
    payload_bytes: int
    sharded: bool

    @property
    def winner_energy_nj(self) -> np.ndarray:
        return self.winner_metrics["energy_nj"]


def _fetch_selection(res, sharded: bool) -> SelectionResult:
    """Materialize the small selection payload (this is the only
    device->host transfer of the fused path) and apply the host-side
    all-non-finite check that `select_best_batch` raises eagerly."""
    has_finite = np.asarray(res["has_finite"])  # repro: host-boundary
    if not has_finite.all():
        raise ValueError(
            "fused selection: a batch cell has no finite energies"
        )
    winner_idx = np.asarray(res["winner_idx"])  # repro: host-boundary
    winner_mets = {k: np.asarray(v) for k, v in res["winner_mets"].items()}  # repro: host-boundary
    nominal_latency = np.asarray(res["nominal_latency"])  # repro: host-boundary
    nominal_fits = np.asarray(res["nominal_fits"])  # repro: host-boundary
    payload = (
        winner_idx.nbytes
        + has_finite.nbytes
        + nominal_latency.nbytes
        + nominal_fits.nbytes
        + sum(v.nbytes for v in winner_mets.values())
    )
    return SelectionResult(
        winner_idx=winner_idx,
        winner_metrics=winner_mets,
        nominal_latency_ns=nominal_latency,
        nominal_fits=nominal_fits,
        payload_bytes=payload,
        sharded=sharded,
    )


def evaluate_select_suite(
    suite: SuiteTable,
    topos: TopologyTable,
    model: "EnergyModel | ModelTable | None" = None,
    mode: str = "physical",
    discipline: str = "list",
    feasible: np.ndarray | None = None,
    max_latency_ns: float | None = None,
    shard: "bool | None" = None,
) -> "tuple[SuiteGrid | SuiteVariationGrid, SelectionResult]":
    """The back half of every sweep: circuits x variants x topologies x
    recipes scheduled, evaluated AND filtered in one jitted device call.
    A single circuit is a one-circuit suite.  Only the ``(C, V)`` winner
    indices + per-winner metrics cross the host boundary; the full
    metric tensors back the returned lazy grid and are materialized
    only on access.

    ``model`` is one `EnergyModel` (a `SuiteGrid`, V=1) or a
    `sram.ModelTable` (a `SuiteVariationGrid`); ``feasible`` is an
    optional ``(n_circuits, n_topologies)`` bool mask of
    capacity-feasible topologies per circuit (Alg. I line 9, default
    all-feasible).

    Winner parity with the host reference (`select_best_batch` over the
    grid's materialized tensors, `SuiteVariationGrid.best_indices`) is
    exact — same tiering, same lowest-flat-index tie-breaking, same
    all-non-finite error — and is pinned by tests/test_fused.py.
    """
    fused_suite = _fused_program()
    table, is_sweep = _as_table(model)
    _check_topo_axis(table, topos)
    feasible = _suite_feasible(suite, topos, feasible)
    use_latency = max_latency_ns is not None
    with jax_env.x64():
        tables = (
            suite.ops, suite.n_levels, topos.ops_per_cycle,
            topos.macros_per_type, topos.is_single, topos.total_bits,
            topos.rows, topos.cols,
        )
        params = _model_params(table)
        max_latency = np.float64(max_latency_ns if use_latency else 0.0)
        replicated = _host_nbytes((tables, feasible, max_latency))
        per_variant = _host_nbytes(params)
        with trace.span("batch.dispatch", h2d_bytes=replicated + per_variant) as span:
            with trace.span("batch.shard") as shard_span:
                params, sharded = _shard_variants(params, shard)
                devices = (len(params.f_clk_hz.sharding.device_set)
                           if sharded else 1)
                shard_span.set_metadata(
                    devices=devices,
                    variants_per_device=len(table) // devices,
                )
            # the variant-sharded model operands reach the devices once;
            # every other operand is replicated to each of them
            span.set_metadata(devices=devices,
                              placed_bytes=replicated * devices + per_variant)
            res = fused_suite(
                *tables, params, feasible, max_latency,
                discipline, mode, use_latency,
            )
        with trace.span("batch.fetch", devices=devices) as span:
            sel = _fetch_selection(res, sharded)
            span.set_metadata(d2h_bytes=sel.payload_bytes)
        sched, mets = _fused_outputs(res)
        grid = _build_suite_grid(
            suite, topos, table, model, is_sweep, mode, discipline,
            feasible, sched, mets,
        )
    return grid, sel


_SELECT_BATCH = None


def _make_select_batch():
    def fn(energy, fits, feasible, latency, max_latency, use_latency):
        TRACE_COUNTS["select_batch"] += 1
        return _select_core(
            energy, fits, feasible, latency, max_latency, use_latency
        )

    return jax.jit(fn, static_argnames=("use_latency",))


def select_best_batch_device(
    energy,
    fits,
    latency=None,
    max_latency: float | None = None,
    feasible=None,
) -> np.ndarray:
    """`select_best_batch` with the three-tier argmin run as a jitted
    device reduction — the standalone fused filter for callers whose
    metrics are already arrays (the mesh explorer's constant sweeps).

    Same semantics as the host version: tiering, lowest-flat-index
    tie-breaking, non-finite energies inadmissible everywhere, ValueError
    on an empty grid or an all-non-finite batch cell.  Absent
    latency/feasible constraints are passed as dummies that drop out of
    the masking algebra (``fits`` as feasible leaves tier 1 == tier 2),
    so only toggling the latency tier — not any operand value —
    retraces.
    """
    global _SELECT_BATCH
    _load_jax()
    if _SELECT_BATCH is None:
        _SELECT_BATCH = _make_select_batch()

    def host_cast(x, dtype):
        # Device arrays (the service's re-rank path) go straight into
        # the jitted reduction — forcing them through np.asarray here
        # would materialize the full (V, N) tensors per request, the
        # exact transfer the device-side selection exists to avoid.
        if isinstance(x, jax.Array):
            return x
        return np.asarray(x, dtype=dtype)  # repro: host-boundary

    energy = host_cast(energy, np.float64)
    if energy.size == 0 or energy.shape[-1] == 0:
        raise ValueError("select_best_batch on an empty grid")
    fits = host_cast(fits, bool)
    use_latency = max_latency is not None and latency is not None
    with jax_env.x64():
        idx, has_finite = _SELECT_BATCH(
            energy,
            fits,
            host_cast(feasible, bool) if feasible is not None else fits,
            # scalar dummy: the use_latency=False graph never reads it,
            # and a scalar avoids shipping the energy array twice
            host_cast(latency, np.float64)
            if use_latency
            else np.float64(0.0),
            np.float64(max_latency if use_latency else 0.0),
            use_latency,
        )
        # winner payload only — (…, V) indices + flags, never the grid
        idx = np.asarray(idx, dtype=np.int64)  # repro: host-boundary
        has_finite = np.asarray(has_finite)  # repro: host-boundary
    if not has_finite.all():
        raise ValueError(
            "select_best_batch: a batch cell has no finite energies"
        )
    return idx


# ---------------------------------------------------------------------------
# Shared admissibility filter + argmin (FilterEnergy)
# ---------------------------------------------------------------------------


def _masked_tier_argmin(energy, tiers, xp=np):
    """Per-batch-cell argmin over the first non-empty tier.

    ``energy``: (..., N); ``tiers``: bool arrays of the same shape, most
    restrictive first.  Each batch cell uses its own first tier with any
    admissible entry; ties break to the lowest index along the last axis
    (``argmin`` returns the first occurrence).  Pure array ops on the
    ``xp`` namespace (numpy by default, ``jax.numpy`` under jit), so the
    mesh/TPU path can fuse the filter after evaluate.
    """
    pool = tiers[-1]
    for tier in tiers[-2::-1]:
        pool = xp.where(tier.any(axis=-1, keepdims=True), tier, pool)
    return xp.argmin(xp.where(pool, energy, xp.inf), axis=-1)


def select_best_batch(
    energy,
    fits,
    latency=None,
    max_latency: float | None = None,
    feasible=None,
) -> np.ndarray:
    """Batched `select_best`: winners for every batch cell in one masked
    three-tier argmin pass — no per-variant python loop.

    ``energy`` is ``(..., N)`` with the candidate implementations along
    the LAST axis (flat C-order, e.g. a raveled topology-major (T, R)
    grid) and arbitrary batch axes in front — ``(V, T*R)`` for one
    circuit's variant sweep, ``(C, V, T*R)`` for a whole suite.
    ``fits`` / ``latency`` / ``feasible`` broadcast against ``energy``,
    so model-free masks are passed once (e.g. ``(C, 1, T*R)``) and serve
    every variant row.

    Tiering, tie-breaking (lowest flat index), and NaN handling are
    exactly `select_best`'s, applied independently per batch cell;
    raises if any batch cell has no finite energy at all.

    Returns int64 winner indices of shape ``energy.shape[:-1]``.
    """
    energy = np.asarray(energy, dtype=float)
    if energy.size == 0 or energy.shape[-1] == 0:
        raise ValueError("select_best_batch on an empty grid")
    finite = np.isfinite(energy)
    if not finite.any(axis=-1).all():
        raise ValueError(
            "select_best_batch: a batch cell has no finite energies"
        )
    tier2 = np.broadcast_to(np.asarray(fits, dtype=bool), energy.shape) & finite
    tier1 = tier2
    if feasible is not None:
        tier1 = tier1 & np.broadcast_to(
            np.asarray(feasible, dtype=bool), energy.shape
        )
    if max_latency is not None and latency is not None:
        tier1 = tier1 & (
            np.broadcast_to(np.asarray(latency, dtype=float), energy.shape)
            <= max_latency
        )
    return _masked_tier_argmin(energy, (tier1, tier2, finite))


def select_best(
    energy,
    fits,
    latency=None,
    max_latency: float | None = None,
    feasible=None,
) -> int:
    """Alg. I line 14 — lowest-energy admissible implementation.

    Args:
        energy: energies, any shape (nJ for the SRAM explorer, J for the
            mesh explorer — only the ordering matters).
        fits: bool mask, same shape — capacity check (4 bits/gate).
        latency: optional latencies (same unit as ``max_latency``; ns for
            the SRAM explorer, s for the mesh explorer).
        max_latency: optional admissibility bound on ``latency``.
        feasible: optional bool mask — Alg. I line 9 topology feasibility.

    Admissibility tiers, in order (first non-empty pool wins, matching
    both `explorer.explore` and `mesh_explorer.explore_mesh`):

      1. fits capacity AND (feasible if given) AND (latency constraint
         if given),
      2. fits capacity,
      3. everything with a finite energy.

    Non-finite energies (NaN / ±inf — e.g. a pathological Monte-Carlo
    variant) are inadmissible in every tier; if *all* energies are
    non-finite there is no winner and a ValueError is raised.

    Returns the flat C-order index of the winner; ties break to the
    lowest flat index, like ``min`` over the scalar evaluation list.

    The single-cell view of `select_best_batch` — one implementation of
    the filter serves the scalar explorers, the variation sweeps, and
    the mesh explorer alike.
    """
    energy = np.asarray(energy, dtype=float).ravel()
    if energy.size == 0:
        raise ValueError("select_best on an empty grid")
    return int(
        select_best_batch(
            energy[None, :],
            np.asarray(fits, dtype=bool).ravel()[None, :],
            latency=None
            if latency is None
            else np.asarray(latency, dtype=float).ravel()[None, :],
            max_latency=max_latency,
            feasible=None
            if feasible is None
            else np.asarray(feasible, dtype=bool).ravel()[None, :],
        )[0]
    )


def winner_summary(winner_keys: Sequence[str]) -> tuple[dict[str, float], float]:
    """Yield arithmetic shared by the SRAM and mesh variation summaries:
    the share of variants each winning implementation takes, and the
    fraction of variants agreeing with the nominal (first) winner."""
    if not winner_keys:
        raise ValueError("winner_summary on an empty sweep")
    counts = collections.Counter(winner_keys)
    n = len(winner_keys)
    share = {k: c / n for k, c in counts.items()}
    return share, counts[winner_keys[0]] / n


def select_best_worst(energy, fits) -> tuple[int, int]:
    """Table I companion: (argmin, argmax) energy over the fitting pool
    (or over everything when nothing fits).  Non-finite energies are
    inadmissible at both ends; all-non-finite raises."""
    energy = np.asarray(energy, dtype=float).ravel()
    if energy.size == 0:
        raise ValueError("select_best_worst on an empty grid")
    finite = np.isfinite(energy)
    if not finite.any():
        raise ValueError("select_best_worst: all energies are non-finite")
    pool = np.asarray(fits, dtype=bool).ravel() & finite
    if not pool.any():
        pool = finite
    best = int(np.argmin(np.where(pool, energy, np.inf)))
    worst = int(np.argmax(np.where(pool, energy, -np.inf)))
    return best, worst


# ---------------------------------------------------------------------------
# Batched Table II metrics (standalone per-topology figures)
# ---------------------------------------------------------------------------


class _BroadcastModel(NamedTuple):
    """`table2_arrays`-compatible view of a `ModelTable` with every field
    shaped (V, 1) — so the same expressions broadcast against (T,)
    topology arrays into (V, T) outputs."""

    f_clk_hz: np.ndarray
    e_op_fj: tuple
    p_ctrl_mw: np.ndarray
    pipeline_utilization: np.ndarray


def table2_batch(
    topos: TopologyTable,
    model: "EnergyModel | ModelTable | None" = None,
    nor_fraction: float = 0.5,
) -> dict[str, np.ndarray]:
    """Vectorized ``sram.table2_metrics`` over a TopologyTable — the same
    ``sram.table2_arrays`` expressions, one array pass.  Outputs are (T,)
    for a single `EnergyModel`, (V, T) for a `ModelTable` of variants
    (whose scalar fields may be per-topology ``(V, T)``)."""
    # `is None`, not falsiness — ModelTable defines __len__, so an `or`
    # here would silently swap a falsy table for the nominal model.
    if model is None:
        model = EnergyModel()
    w = topos.ops_per_cycle.astype(float) * topos.n_macros
    if isinstance(model, ModelTable):
        _check_topo_axis(model, topos)
        e3 = model.e_op_fj  # (V, 3) -> (V, 1) columns; (V, T, 3) -> (V, T)
        shim = _BroadcastModel(
            f_clk_hz=_per_topo(model.f_clk_hz),
            e_op_fj=tuple(
                (e3[:, :, k] if e3.ndim == 3 else e3[:, k: k + 1])
                for k in range(3)
            ),
            p_ctrl_mw=_per_topo(model.p_ctrl_mw),
            pipeline_utilization=_per_topo(model.pipeline_utilization),
        )
        return table2_arrays(
            w[None, :], topos.area_mm2(model), shim, nor_fraction
        )
    return table2_arrays(w, topos.area_mm2(model), model, nor_fraction)


# ---------------------------------------------------------------------------
# Kernel registration (static analyzer)
# ---------------------------------------------------------------------------
# Each builder returns a *fresh* jit wrapper plus small-but-representative
# operands; `repro.analysis.jaxpr_lint` abstract-traces through them (no
# device work) to verify the trace-counter, dtype, const, and donation
# discipline of every kernel at lint time.


def _ex_fused_suite():
    """The fused program on tiny but shape-representative operands: C=2
    circuits, R=2 recipes, L=4 levels, T=2 topologies, V=2 model
    variants — the same dtypes and axis layout production tables carry."""
    _load_jax()
    lvl = np.array(
        [[2, 1, 0], [1, 0, 1], [1, 2, 1], [0, 1, 1]], dtype=np.int32
    )                                                    # (L, 3)
    ops = np.stack([lvl, lvl[::-1]])                     # (R, L, 3)
    v = 2
    params = ModelParams(
        f_clk_hz=np.full((v,), 1.0e9),
        e_op_marginal_fj=np.full((v, 3), 5.0),
        p_ctrl_mw=np.full((v,), 0.1),
        e_macro_cycle_fj=np.full((v,), 10.0),
        e_col_cycle_fj=np.full((v,), 1.0),
        alpha_mw_per_level=np.full((v,), 0.01),
        pipeline_utilization=np.full((v,), 0.9),
    )
    return _registry.KernelExample(
        fn=_make_fused_suite(),
        args=(
            np.stack([ops, ops]),                        # (C, R, L, 3)
            np.array([[4, 3], [3, 4]], dtype=np.int32),  # n_levels (C, R)
            np.array([4, 8], dtype=np.int32),            # width
            np.array([[1, 1, 1], [2, 1, 1]], dtype=np.int32),  # mpt
            np.array([True, False]),                     # is_single
            np.array([1024, 4096], dtype=np.int32),      # total_bits
            np.array([16, 32], dtype=np.int32),          # rows
            np.array([16, 32], dtype=np.int32),          # cols
            params,
            np.ones((2, 2), dtype=bool),                 # feasible (C, T)
            np.float64(1.0e6),                           # max_latency
        ),
        statics={
            "discipline": "list", "mode": "physical", "use_latency": True,
        },
    )


def _ex_select_batch():
    _load_jax()
    energy = np.array([[1.0, 2.0, 3.0], [3.0, 1.0, 2.0]])     # (V, N)
    masks = np.array([[True, True, False]])                    # (1, N)
    latency = np.full((2, 3), 5.0)
    return _registry.KernelExample(
        fn=_make_select_batch(),
        args=(energy, masks, masks, latency, np.float64(10.0)),
        statics={"use_latency": True},
    )


_registry.register_kernel("fused_suite", __name__, _ex_fused_suite)
_registry.register_kernel("select_batch", __name__, _ex_select_batch)
