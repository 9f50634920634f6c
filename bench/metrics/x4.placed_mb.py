"""Megabytes (1e6 bytes) that reach the chips for the fused suite
program (``placed_bytes`` of the ``rcim.batch.dispatch`` spans: each
replicated operand once per chip, each variant-sharded operand once) per
`explore_suite` call of the traced window.  A program whose spans do not
count it reads nothing."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    placed = [sp.args["placed_bytes"] for sp in ps.named("rcim.batch.dispatch")
              if "placed_bytes" in sp.args] if calls else []
    if not placed:
        return None
    return sum(placed) / calls / 1e6
