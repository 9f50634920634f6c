"""Megabytes (1e6 bytes) of host operands the fused suite program is
given (``h2d_bytes`` of the ``rcim.batch.dispatch`` spans) per
`explore_suite` call of the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    if not calls:
        return None
    dispatch = ps.named("rcim.batch.dispatch")
    return sum(sp.args.get("h2d_bytes", 0) for sp in dispatch) / calls / 1e6
