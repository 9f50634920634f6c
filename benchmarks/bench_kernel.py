"""CiM engine microbenchmark (§III-B execution model).

Times the Pallas bit-plane kernel (interpret mode on CPU — wall numbers are
for regression tracking, not TPU projections) and cross-checks the rCiM
analytical model's prediction for the same workload: ops/cycle, energy, and
the modeled speedup of the in-VMEM evaluation vs per-level HBM round-trips.

Runs standalone (``python -m benchmarks.bench_kernel``) or from
``benchmarks.run``; either way the numbers are merged into
``BENCH_explorer.json`` under a ``"kernel"`` key with the same
merge-preserving write the other benches use, so the kernel-level
regression record lives next to the explorer/variation sections instead
of only in the CSV mirror.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import circuits as C
from repro.core.mapping import schedule_stats
from repro.core.sram import EnergyModel, SramTopology, evaluate
from repro.kernels import ops

from .common import Csv, merge_json, timeit


def run(csv: Csv, out_json: str = "BENCH_explorer.json") -> dict:
    em = EnergyModel()
    rng = np.random.default_rng(0)
    record: dict = {"per_circuit": {}}
    for name, gen, n_vec in [
        ("adder16", lambda: C.gen_adder(16), 8192),
        ("mult8", lambda: C.gen_multiplier(8), 4096),
        ("max8x4", lambda: C.gen_max(8, 4), 8192),
    ]:
        aig = gen()
        net = aig.to_gate_netlist()
        cc = ops.compile_netlist(net)
        bits = rng.integers(0, 2, size=(aig.n_pis, n_vec)).astype(np.uint8)
        packed = ops.ref.pack_vectors(bits)

        us = timeit(ops.cim_evaluate, cc, packed, packed=True,
                    block_words=128, n_warmup=1, n_iter=3)
        gate_evals = cc.n_gates * n_vec
        # analytical rCiM prediction for the same netlist on an 8KB macro
        st = aig.characterize()
        topo = SramTopology(8, 1)
        met = evaluate(schedule_stats(st, topo), topo, em)
        record["per_circuit"][name] = dict(
            us=round(us, 1),
            n_gates=cc.n_gates,
            n_rows=cc.n_rows,
            reuse_factor=round(cc.reuse_factor, 2),
            n_vectors=n_vec,
            geval_per_s_m=round(gate_evals / (us * 1e-6) / 1e6, 1),
            model_cycles=int(met.cycles),
            model_energy_nj=round(met.energy_nj, 4),
            model_throughput_gops=round(met.throughput_gops, 1),
        )
        csv.add(
            f"kernel/{name}", us,
            f"gates={cc.n_gates};rows={cc.n_rows}(reuse {cc.reuse_factor:.1f}x);"
            f"vec={n_vec};geval_per_s={gate_evals/(us*1e-6)/1e6:.1f}M;"
            f"rcim_model:cycles={met.cycles},E={met.energy_nj:.4f}nJ,"
            f"thr={met.throughput_gops:.0f}GOPS",
        )

    # VMEM-residency claim: the modeled HBM round-trip cost per level vs
    # keeping bit-planes resident (DESIGN.md memory-hierarchy mapping).
    aig = C.gen_adder(16)
    cc = ops.compile_netlist(aig.to_gate_netlist())
    n_vec = 8192
    bytes_planes = cc.n_rows * n_vec // 8
    levels = aig.characterize().n_levels
    hbm_bw, vmem_bw = 819e9, 20e12  # v5e HBM vs ~VMEM bandwidth
    t_roundtrip = 2 * bytes_planes * levels / hbm_bw
    t_resident = 2 * bytes_planes * levels / vmem_bw
    record["vmem_residency"] = dict(
        levels=levels,
        bytes_planes=bytes_planes,
        modeled_speedup=round(t_roundtrip / t_resident),
    )
    csv.add("kernel/vmem_residency_model", 0.0,
            f"levels={levels};modeled_speedup={t_roundtrip/t_resident:.0f}x")

    # Merge-preserving write: the other benches own sibling top-level
    # keys in the same json.
    merge_json(out_json, {"kernel": record})
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_explorer.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(Csv(), out_json=args.out)


if __name__ == "__main__":
    main()
