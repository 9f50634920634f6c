"""Jit traces of the cone-simulation kernels inside the window
(`analysis.registry.trace_counts` deltas): shapes set-up did not warm."""

KERNELS = ("aig_eval", "aig_eval_pallas", "aig_sig")


def read(m):
    traces = m["counters"].get("traces", {})
    return sum(traces.get(k, 0) for k in KERNELS)
