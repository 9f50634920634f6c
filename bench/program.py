"""The benchmark's inputs in the program's own types: the program's
objects built from the benchmark's data and nothing else."""

from __future__ import annotations

import reference as ref


def topologies(spec: dict) -> list:
    """`SramTopology` objects of a configuration's topology block, named
    exactly as the reference names them."""
    from repro.core.sram import SramTopology

    out = []
    for t in ref.topologies(spec):
        if spec["kind"] == "library":
            p = SramTopology(t["total_kb"] // t["n_macros"], t["n_macros"])
        else:
            p = SramTopology.from_geometry(t["rows"], t["cols"], t["n_macros"])
        if p.name != t["name"]:
            raise ValueError(f"topology {p.name} != reference {t['name']}")
        out.append(p)
    return out


def model_table(arrays: dict):
    """A `ModelTable` holding the benchmark's variant arrays (row 0
    nominal)."""
    from repro.core.sram import ModelTable

    n = len(arrays["f_clk_hz"])
    return ModelTable(names=tuple(f"v{i}" for i in range(n)), **arrays)


def energy_model(config: dict):
    """The configuration's nominal `EnergyModel`."""
    from repro.core.sram import EnergyModel

    m = config["energy_model"]
    return EnergyModel(**{f: (tuple(m[f]) if f in ref.PER_OP_FIELDS else m[f])
                          for f in ref.MODEL_FIELDS})


def suite(nets: dict) -> dict:
    from repro.core.aig import Aig

    return {n: Aig.from_dict(d) for n, d in nets.items()}


def cha(frozen: dict) -> dict:
    from repro.core.aig import AigStats

    return {n: {r: AigStats.from_dict(s) for r, s in rows.items()}
            for n, rows in frozen.items()}
