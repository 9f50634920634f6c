#!/usr/bin/env python3
"""The controls of the benchmark's correctness checks, at each cell's
own size, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

A control has to come out as not correct, or the check could not tell a
wrong answer from a right one.

* Sweep and service cells: the plain reference put in the program's
  place, computed in float32 (the precision below the float64 the
  configuration states) on the device through ``jax.numpy``; its answers
  are scored by the float64 reference exactly as a run scores the
  program's, over as many answers as a run checks.
* The characterization cell states no precision; its control breaks one
  guarantee the configuration states, that every transform application
  preserves the circuit's function, by complementing the first output of
  every rewrite (Rw) result where it is produced, and runs the cell's
  set-up and window with that in place.  ``--fault`` runs it with one of
  the faults of `FAULTS` in place instead: transforms that return their
  input unchanged, or that keep half of their cuts.

Prints one JSON line per seed: the numbers compared and their limits.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


@contextlib.contextmanager
def broken_rewrite():
    """Every rewrite result leaves with its first output complemented."""
    from repro.core import transforms

    orig = transforms.rewrite

    def rewrite(aig, *a, **kw):
        out = orig(aig, *a, **kw)
        if out.pos:
            out.pos[0] ^= 1
        return out

    transforms.rewrite = rewrite
    try:
        yield
    finally:
        transforms.rewrite = orig


@contextlib.contextmanager
def transforms_do_nothing():
    """Every transform returns its input unchanged."""
    from repro.core import transforms

    orig = transforms.transform_fns
    transforms.transform_fns = lambda backend="python": {
        t: (lambda aig: aig) for t in transforms.TRANSFORM_NAMES}
    try:
        yield
    finally:
        transforms.transform_fns = orig


@contextlib.contextmanager
def half_the_cuts():
    """Rewrite keeps half of its cuts per node, refactor cuts half as wide."""
    from repro.core import transforms

    rewrite, refactor = transforms.rewrite, transforms.refactor
    transforms.rewrite = lambda aig, *a, **kw: rewrite(aig, *a, **dict(kw, max_cuts=4))
    transforms.refactor = lambda aig, *a, **kw: refactor(aig, *a, **dict(kw, max_leaves=5))
    try:
        yield
    finally:
        transforms.rewrite, transforms.refactor = rewrite, refactor


#: The characterization cell's control and the faults it can have.
FAULTS = {"broken-rewrite": broken_rewrite, "no-op": transforms_do_nothing,
          "half-cuts": half_the_cuts}


def readings(root: Path, workload: str, seed: int, seconds: float,
             fault: str = "broken-rewrite") -> dict:
    """The control's numbers for one seed, each with its limit."""
    import jax.numpy as jnp
    import numpy as np

    _spec, ctx, generator = run.prepare(root, workload, seed, seconds, False)
    kind = ctx.traffic["generator"]
    if kind == "sweep":
        state = generator.reference_state(ctx)
        refs = generator.reference_setup(ctx, state)
        calls = [dict(index=i, answers=generator.reference_answers(
                     ctx, state, refs, i, jnp, np.float32))
                 for i in range(generator.MAX_CHECKED_CALLS)]
        return {"winner_energy_rel_err": [generator.score(ctx, state, refs, calls),
                                          generator.WINNER_ENERGY_REL_ERR_LIMIT]}
    if kind == "serve":
        state = generator.reference_state(ctx)
        worst, wrong = generator.score(ctx, state,
                                    generator.reference_answers(ctx, state, jnp, np.float32))
        return {"winner_energy_rel_err": [worst, generator.WINNER_ENERGY_REL_ERR_LIMIT],
                "wrong_answers": [wrong, generator.WRONG_ANSWERS_LIMIT]}
    if kind == "characterize":
        with FAULTS[fault]():
            state = generator.setup(ctx)
            win = generator.window(ctx, state)
        return {c.name: [c.value, c.limit] for c in generator.check(ctx, state, win)}
    raise ValueError(f"no control for generator {kind!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="broken-rewrite",
                    help="characterization cells: what the run has in place")
    args = ap.parse_args(argv)
    root = BENCH.parent
    run._jax_env(root)
    import jax

    dev = jax.devices()[0]
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = readings(root, args.workload, seed, args.seconds, args.fault)
        print(json.dumps({"workload": args.workload, "seed": seed, "fault": args.fault,
                          "platform": dev.platform, "kind": dev.device_kind,
                          "control": out,
                          "fails": any(v > lim for v, lim in out.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
