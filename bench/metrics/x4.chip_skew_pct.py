"""How unevenly the chips run the variant-sharded fused suite program:
100 x (slowest - fastest chip's program time) / slowest, over the traced
window.  A run on fewer than two chips reads nothing."""

#: The fused suite program, as `x4.fused_ms` finds it.
PROGRAM = "jit_fn("


def read(m):
    per_dev = list(m["trace"].program_seconds(PROGRAM).values())
    if len(per_dev) < 2 or max(per_dev) <= 0:
        return None
    return 100.0 * (max(per_dev) - min(per_dev)) / max(per_dev)
