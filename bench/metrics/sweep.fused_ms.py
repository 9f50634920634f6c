"""Device time of the fused suite program per `explore_suite` call, from
the trace; on several chips, the slowest device's."""

#: Name of the fused evaluate+select suite program in the trace: the
#: jitted inner function ``fn`` of `core/batch.py` `_make_fused_suite`.
#: In this cell's window it is the only ``fn`` program (one per call).
PROGRAM = "jit_fn("


def read(m):
    red = m["trace"]
    calls = red.spans_named("bench.sweep.call")
    per_dev = red.program_seconds(PROGRAM)
    if not calls or not per_dev or max(per_dev.values()) <= 0:
        return None
    return max(per_dev.values()) / len(calls) * 1e3
