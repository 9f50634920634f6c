import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

For every cell this driver:
    1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
    2. builds ShapeDtypeStruct stand-ins (no allocation) for params,
       optimizer state, inputs, and KV caches,
    3. jit(step, in_shardings=...).lower(...).compile(),
    4. records memory_analysis / cost_analysis / collective schedule,
    5. derives the three roofline terms (launch/roofline.py) and appends a
       JSON record to runs/dryrun/ (idempotent: cells already recorded are
       skipped, so a killed run resumes where it left off).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 2-pod pass
"""

import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, overrides: dict | None = None,
             tag: str = "", mesh_shape: tuple | None = None) -> dict:
    import jax

    from repro.configs import SKIP_CELLS, get_config
    from repro.launch.hloparse import analyze
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.launch.roofline import CollectiveStats, model_flops, roofline_terms
    from repro.launch.specs import CellSpec
    from repro.models.config import SHAPES

    mesh_name = "multi" if multi_pod else "single"
    key = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    if (arch, shape_name) in SKIP_CELLS:
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                   skipped=SKIP_CELLS[(arch, shape_name)])
        _write(path, rec)
        return rec

    t0 = time.time()
    if mesh_shape is not None:
        axes = ("pod", "data", "model") if len(mesh_shape) == 3 else ("data", "model")
        mesh = make_mesh(mesh_shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cell = CellSpec(arch, shape_name, mesh, **(overrides or {}))
    fn, args, shards, donate = cell.step_fn_and_args()

    with mesh:
        lowered = jax.jit(fn, in_shardings=shards, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # newer jax returns a single dict; older returned [dict] per program
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()

    # Trip-count-corrected HLO costs (XLA's cost_analysis counts while
    # bodies once — see launch/hloparse.py).
    hc = analyze(hlo, default_group=mesh.shape.get("model", 16))
    coll = CollectiveStats(total_link_bytes=hc.link_bytes,
                           by_kind=hc.coll_by_kind, n_ops=hc.n_collectives)
    mf = model_flops(cell.cfg, SHAPES[shape_name])
    rl = roofline_terms(
        {"flops": hc.flops, "bytes accessed": hc.hbm_bytes}, coll, n_chips, mf
    )

    mem_rec = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        mem_rec[field] = getattr(mem, field, None)
    hbm_per_device = (
        (mem_rec.get("argument_size_in_bytes") or 0)
        + (mem_rec.get("temp_size_in_bytes") or 0)
        - (mem_rec.get("alias_size_in_bytes") or 0)  # donated buffers alias args
    )

    rec = dict(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        tag=tag,
        n_chips=int(n_chips),
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem_rec,
        hbm_per_device_gb=round(hbm_per_device / 2**30, 3),
        cost=dict(flops=float(cost.get("flops", 0.0)),
                  bytes_accessed=float(cost.get("bytes accessed", 0.0)),
                  note="raw XLA cost_analysis (while bodies counted once)"),
        roofline=rl.as_dict(),
        n_collectives=coll.n_ops,
        trip_counts=hc.trip_counts,
    )
    _write(path, rec)
    return rec


def _write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
    os.rename(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS
    from repro.models.config import SHAPES

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                label = f"{arch:22s} {shape:12s} {'multi' if multi else 'single'}"
                try:
                    rec = run_cell(arch, shape, multi, args.out, force=args.force)
                    if "skipped" in rec:
                        n_skip += 1
                        print(f"SKIP {label}: {rec['skipped']}", flush=True)
                    else:
                        n_ok += 1
                        r = rec["roofline"]
                        print(
                            f"OK   {label}: hbm/dev={rec['hbm_per_device_gb']:.2f}GB "
                            f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                            f"coll={r['collective_s']:.4f}s -> {r['bottleneck']} "
                            f"(compile {rec['compile_s']:.0f}s)",
                            flush=True,
                        )
                except Exception as e:  # noqa: BLE001 — a failed cell is a bug to report
                    n_fail += 1
                    print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
