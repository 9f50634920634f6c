#!/usr/bin/env python3
"""Find a service mix's knee: the highest arrival rate the warm service
sustains without a growing backlog.

    python3 bench/knee.py --config epfl9-lib12 --traffic serve.rerank \\
        --seed <n> --seconds <s> --rates 50,100,200,...

One process, one set-up, then one window per rate (the mix with only
``rate_per_s`` changed).  Prints one JSON line per
rate: offered and completed rates, median and 95th-percentile latency,
the 95th percentile over the first and the last fifth of the requests
(a backlog that grows shows as the last fifth's tail running away), and
the failures.  This is a one-time measurement for choosing the rate the
cell fixes; the benchmark's runs never call it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = BENCH.parent
    cell = {"name": args.traffic, "config": args.config, "traffic": args.traffic, "chips": 1}
    _spec, ctx, generator = run.prepare(root, cell, args.seed, args.seconds, False)
    run._jax_env(root)
    run._devices(root, ctx.cell["chips"], require_tpu=True)
    state = generator.setup(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx.traffic = dict(ctx.traffic, rate_per_s=rate)
        state["schedule"] = generator.schedule(ctx, state)
        win = generator.offer(ctx, state)
        lat = win.state["latency_ms"]
        k = max(1, len(lat) // 5)
        print(json.dumps({
            "rate_per_s": rate,
            "requests": win.attempted,
            "failed": win.failed,
            "completed_per_s": (win.attempted - win.failed) / max(win.seconds, 1e-9),
            "p50_ms": common.percentile(lat, 50),
            "p95_ms": common.percentile(lat, 95),
            "p95_first_fifth_ms": common.percentile(lat[:k], 95),
            "p95_last_fifth_ms": common.percentile(lat[-k:], 95),
            "gen_late_p95_ms": common.percentile(ctx.counters["gen_late_ms"], 95),
        }), flush=True)
    state.pop("svc").close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
