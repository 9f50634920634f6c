"""Device time of the collective operations (the chips exchanging data
inside the partitioned program) per `explore_suite` call of the traced
window, on the chip that spent most in them.  Operations are matched by
the names the trace gives them."""

#: Substrings of the trace's names for XLA's collectives, their async
#: ``-start``/``-done`` halves included.
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")


def read(m):
    red = m["trace"]
    calls = red.spans_named("bench.sweep.call")
    if not calls or not red.ops:
        return None
    per_dev = [sum(s for name, s in ops.items() if any(c in name for c in COLLECTIVES))
               for ops in red.ops.values()]
    return max(per_dev) / len(calls) * 1e3
