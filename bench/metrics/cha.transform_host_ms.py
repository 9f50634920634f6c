"""Host time of the transforms outside the cone simulation: the
``rcim.cha.apply`` spans (a transform and its output's fingerprint) less
the ``rcim.aig_sim.*`` spans inside them, per transform application
finished in the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    apps = m["counters"].get("applications", 0)
    if ps is None or not apps:
        return None
    applies = ps.named("rcim.cha.apply")
    return sum(sp.seconds - ps.covered_s(sp, "rcim.aig_sim.") for sp in applies) / apps * 1e3
