"""Benchmark harness — one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (mirrored to runs/bench/).

    PYTHONPATH=src python -m benchmarks.run                 # fast (tiny suite)
    PYTHONPATH=src python -m benchmarks.run --scale default # paper-scale circuits
    PYTHONPATH=src python -m benchmarks.run --only fig9,kernel
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "default", "paper"], default="tiny")
    ap.add_argument("--only", default=None,
                    help="comma list: fig9,table1,table2,kernel,"
                         "roofline,explorer,characterization,service,"
                         "system,faults")
    args = ap.parse_args()
    which = set(args.only.split(",")) if args.only else {
        "fig9", "table1", "table2", "kernel", "roofline",
        "explorer", "characterization", "service", "system", "faults",
    }

    from .common import Csv

    csv = Csv()
    # One persistent characterization cache shared by every bench that
    # runs the Algorithm-I front half (fig9's and table1's sweeps reuse
    # each other's transforms; reruns of the harness start warm).
    cache = "runs/cha_cache"
    print("name,us_per_call,derived")
    if "fig9" in which:
        from . import bench_fig9

        bench_fig9.run(csv, scale=args.scale, cache=cache)
    if "table1" in which:
        from . import bench_table1

        bench_table1.run(csv, scale=args.scale, cache=cache)
    if "table2" in which:
        from . import bench_table2

        bench_table2.run(csv)
    if "kernel" in which:
        from . import bench_kernel

        # merged into BENCH_explorer.json under "kernel" alongside the
        # explorer / characterization sections
        bench_kernel.run(csv, out_json="BENCH_explorer.json")
    if "characterization" in which:
        from . import bench_characterization

        # front-half device-vs-python record, merged under
        # "characterization" in BENCH_explorer.json
        bench_characterization.run(
            csv, scale=args.scale, out_json="BENCH_explorer.json",
            serial_reference=False,
        )
    if "service" in which:
        from . import bench_service

        # warm persistent query engine: cold/warm latency, rps, trace
        # accounting — merged under "service" in BENCH_explorer.json
        bench_service.run_service_bench(
            csv, scale=args.scale, cache_dir=cache,
            out_json="BENCH_explorer.json",
        )
    if "roofline" in which:
        from . import bench_roofline

        bench_roofline.run(csv)
    if "system" in which:
        from . import bench_system

        # workload-lowered rCiM vs conventional roofline per token —
        # merged under "system" in BENCH_explorer.json
        bench_system.run(csv, scale=args.scale, out_json="BENCH_explorer.json")
    if "faults" in which:
        from . import bench_faults

        # journal overhead + crash-recovery latency of the resumable
        # sweep — merged under "faults" in BENCH_explorer.json
        bench_faults.run(csv, scale=args.scale, cache=cache,
                         out_json="BENCH_explorer.json")
    if "explorer" in which:
        from . import bench_explorer

        bench_explorer.run(csv, scale=args.scale)
    csv.save("bench.csv")


if __name__ == "__main__":
    main()
