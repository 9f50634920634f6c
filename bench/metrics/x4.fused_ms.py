"""Device time of the variant-sharded fused suite program per
`explore_suite` call of the traced window, on the slowest chip."""

#: The fused evaluate+select suite program, partitioned over the chips:
#: the jitted ``fn`` of `core/batch.py` `_make_fused_suite`, the only
#: ``fn`` program of the window.
PROGRAM = "jit_fn("


def read(m):
    red = m["trace"]
    calls = red.spans_named("bench.sweep.call")
    per_dev = red.program_seconds(PROGRAM)
    if not calls or not per_dev or max(per_dev.values()) <= 0:
        return None
    return max(per_dev.values()) / len(calls) * 1e3
