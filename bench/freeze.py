#!/usr/bin/env python3
"""Make a configuration's frozen data under ``bench/data`` (run once, on
any host; the benchmark's runs only read what it wrote).

    python3 bench/freeze.py netlists bench/configs/<config>.json
    python3 bench/freeze.py cha      bench/configs/<config>.json [--jobs 4]
    python3 bench/freeze.py expected bench/configs/<config>.json [--jobs 4]
    python3 bench/freeze.py warm     bench/configs/<config>.json [--jobs 4]

* ``netlists``: the configuration's circuits at its widths, built by the
  program's generators (`repro.core.circuits`), as fanin-literal dicts,
  to the file the configuration's ``netlists`` names.  Each circuit's
  AND count must equal the configuration's ``and_nodes``.
* ``cha``: the AigStats of every (circuit, recipe), made by the program's
  python characterization backend, to ``frozen_cha``: the input of the
  sweep and service cells, so that no front-half change alters it.
* ``expected``: the output fingerprint of every (circuit, recipe), made
  by the benchmark's own reference transforms (`ref_transforms`), to
  ``expected_outputs``: what the characterization cell's check compares
  each application its window persisted with.
* ``warm``: every circuit's output after each single transform, made by
  the reference transforms, to ``warm_graphs``: the characterization
  cell's set-up warms the cone-simulation programs of these graphs'
  shapes, which its window meets as the sources of recipes of length 2.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import reference as ref  # noqa: E402
import ref_transforms  # noqa: E402

#: Generator keyword of each width key of a configuration.
WIDTH_KEYS = {
    "adder": {"adder_bits": "n"}, "bar": {"bar_bits": "n"},
    "mult": {"mult_bits": "n"}, "sine": {"sine_bits": "n"},
    "max": {"max_bits": "n", "max_inputs": "k"}, "div": {"div_bits": "n"},
    "sqrt": {"sqrt_bits": "n"}, "square": {"square_bits": "n"},
    "log2": {"log2_bits": "n", "log2_frac_bits": "frac_bits"},
}


def _write(rel: str, payload: dict) -> None:
    with gzip.open(ROOT / rel, "wt") as f:
        json.dump(payload, f, separators=(",", ":"))
    print(f"wrote {rel}", flush=True)


def netlists(config: dict) -> None:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import circuits

    out = {}
    for name in config["circuits"]:
        kw = {arg: config[key] for key, arg in WIDTH_KEYS[name].items()}
        aig = circuits._GENERATORS[name](**kw)
        if aig.n_ands != config["and_nodes"][name]:
            raise SystemExit(f"{name}: {aig.n_ands} ANDs, the configuration "
                             f"says {config['and_nodes'][name]}")
        out[name] = aig.to_dict()
    _write(config["netlists"], {"format": "aig-fanin-literals-v1", "circuits": out})


def cha(config: dict, jobs: int) -> None:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.aig import Aig
    from repro.core.transforms import TRANSFORM_VERSION, characterize_suite

    nets = common.load_json(ROOT / config["netlists"])["circuits"]
    recipes = ref.recipes(config["recipes"])
    got = characterize_suite({n: Aig.from_dict(nets[n]) for n in config["circuits"]},
                             recipes[1:], n_jobs=jobs, backend="python")
    _write(config["frozen_cha"], {
        "transform_version": TRANSFORM_VERSION,
        "circuits": {n: {",".join(r): got[n][r].to_dict() for r in recipes}
                     for n in config["circuits"]},
    })


def _expected(args):
    d, recipes = args
    return ref_transforms.expected_outputs(d, recipes)


def expected(config: dict, jobs: int) -> None:
    nets = common.load_json(ROOT / config["netlists"])["circuits"]
    recipes = ref.recipes(config["recipes"])
    names = config["circuits"]
    with ProcessPoolExecutor(jobs) as ex:
        fps = list(ex.map(_expected, [(nets[n], recipes) for n in names]))
    _write(config["expected_outputs"], {
        "transform_version": ref_transforms.TRANSFORM_VERSION,
        "circuits": dict(zip(names, fps)),
    })


def _depth1(d: dict) -> dict:
    base = ref_transforms.Aig.from_dict(d)
    return {t: fn(base).to_dict() for t, fn in ref_transforms.TRANSFORMS.items()}


def warm(config: dict, jobs: int) -> None:
    nets = common.load_json(ROOT / config["netlists"])["circuits"]
    names = config["circuits"]
    with ProcessPoolExecutor(jobs) as ex:
        outs = list(ex.map(_depth1, [nets[n] for n in names]))
    _write(config["warm_graphs"], {
        "format": "aig-fanin-literals-v1",
        "transform_version": ref_transforms.TRANSFORM_VERSION,
        "circuits": dict(zip(names, outs)),
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("netlists", "cha", "expected", "warm"))
    ap.add_argument("config")
    ap.add_argument("--jobs", type=int, default=4)
    args = ap.parse_args(argv)
    config = common.load_json(Path(args.config).resolve())
    if args.what == "netlists":
        netlists(config)
    elif args.what == "cha":
        cha(config, args.jobs)
    elif args.what == "warm":
        warm(config, args.jobs)
    else:
        expected(config, args.jobs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
