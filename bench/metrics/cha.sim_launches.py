"""Cone-simulation device calls (``rcim.aig_sim.launch`` spans) per
transform application finished in the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    apps = m["counters"].get("applications", 0)
    if ps is None or not apps:
        return None
    return len(ps.named("rcim.aig_sim.launch")) / apps
