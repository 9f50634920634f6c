"""Pallas TPU kernel: CiM bit-plane boolean logic engine.

TPU adaptation of the paper's in-SRAM computing (§III-B): the entire
combinational evaluation happens inside VMEM — the TPU's on-chip SRAM —
with zero HBM round-trips between logic levels.  The memory-hierarchy
mapping is

    DRAM -> SRAM array -> bitlines      (paper)
    HBM  -> VMEM scratch -> VREGs       (here)

and the architectural knobs line up one-to-one with the paper's topology
space (core/mesh_explorer.py searches them the way Alg. I searches SRAM
topologies):

    grid tiles over packed test vectors  <->  parallel macros
    ``block_words`` (lanes per tile)     <->  bank column count M
    scratch rows (register file)         <->  SRAM rows
    instruction stream                   <->  wordline-activation schedule

One instruction = one macro op: two row reads (the dual read ports), a
NAND2/NOR2/NOT on 8x128-lane VREG tiles, one row writeback.  Row indices
come from ops.compile_netlist, which performs the paper's operand placement
(with linear-scan row reuse standing in for "operands placed flexibly
within the two columns").

Kernel layout:
  * instrs  (4 (n_gates + n_pos),) int32 in SMEM (scalar prefetch) —
    flat [kind, a_row, b_row, out_row] words, read as scalars
  * pi      (n_rows_padded, block_words) — PI planes pre-placed in rows
  * out     (n_po_padded, block_words)   — gathered PO planes
  * scratch (n_rows_padded, block_words) VMEM — the "SRAM array"

The kernel compiles with Mosaic on a TPU and runs in interpret mode on
every other backend (`runtime.jax_env.pallas_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import registry as _registry
from repro.runtime import jax_env

# repro: kernel-module
TRACE_COUNTS = _registry.TRACE_COUNTS
_registry.register_counter("cim_pallas", __name__)

LANE = 128
SUBLANE = 8


def trace_counts() -> dict[str, int]:
    """Snapshot of this module's jit trace counters."""
    return _registry.trace_counts(module=__name__)


def _cim_kernel(instr_ref, pi_ref, out_ref, scratch_ref, *, n_gates: int, n_pos: int):
    # Load the PI planes (pre-placed into their rows by the host wrapper)
    # into the VMEM "SRAM array".
    scratch_ref[...] = pi_ref[...]

    def step(i, _):
        kind = instr_ref[4 * i]
        a_row = instr_ref[4 * i + 1]
        b_row = instr_ref[4 * i + 2]
        o_row = instr_ref[4 * i + 3]
        a = scratch_ref[pl.ds(a_row, 1), :]
        b = scratch_ref[pl.ds(b_row, 1), :]
        res = jnp.bitwise_not(jnp.where(kind == 1, a | b, a & b))
        scratch_ref[pl.ds(o_row, 1), :] = res
        return 0

    jax.lax.fori_loop(0, n_gates, step, 0)

    # Gather POs: instruction slots [n_gates, n_gates + n_pos) carry the PO
    # row index in column 3 (kind = 3 sentinel).
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def gather(j, _):
        row = instr_ref[4 * (n_gates + j) + 3]
        out_ref[pl.ds(j, 1), :] = scratch_ref[pl.ds(row, 1), :]
        return 0

    jax.lax.fori_loop(0, n_pos, gather, 0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=None)
def _cim_call(interpret: bool):
    """The jitted kernel call, built once per interpret flag (the compile
    tests build the Mosaic one on a host without a TPU)."""

    @functools.partial(
        jax.jit, static_argnames=("n_rows", "n_gates", "n_pos", "block_words")
    )
    def call(instrs, pi_planes, n_rows: int, n_gates: int, n_pos: int,
             block_words: int):
        TRACE_COUNTS["cim_pallas"] += 1
        n_rows_p, n_words = pi_planes.shape
        assert n_rows_p == _round_up(n_rows, SUBLANE)
        assert n_words % block_words == 0, (n_words, block_words)
        n_pos_p = _round_up(n_pos, SUBLANE)
        return pl.pallas_call(
            functools.partial(_cim_kernel, n_gates=n_gates, n_pos=n_pos),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_words // block_words,),
                in_specs=[
                    pl.BlockSpec((n_rows_p, block_words), lambda j, _: (0, j)),
                ],
                out_specs=pl.BlockSpec(
                    (n_pos_p, block_words), lambda j, _: (0, j)
                ),
                # VMEM scratch: the "SRAM array".
                scratch_shapes=[pltpu.VMEM((n_rows_p, block_words), jnp.int32)],
            ),
            out_shape=jax.ShapeDtypeStruct((n_pos_p, n_words), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
        )(jnp.reshape(instrs, (-1,)), pi_planes)

    return call


def cim_pallas_call(
    instrs: jax.Array,  # (n_gates + n_pos, 4) int32 (PO gather slots appended)
    pi_planes: jax.Array,  # (n_rows_padded, n_words) int32, PIs pre-placed
    n_rows: int,
    n_gates: int,
    n_pos: int,
    block_words: int = 512,
):
    """Run the CiM engine over ``pi_planes`` in ``block_words``-lane tiles."""
    return _cim_call(jax_env.pallas_interpret())(
        instrs, pi_planes, n_rows=n_rows, n_gates=n_gates, n_pos=n_pos,
        block_words=block_words,
    )
