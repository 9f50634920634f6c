"""Host time of the cone simulation (the ``rcim.aig_sim.*`` spans:
compile, operand packing, launches up to their results on the host,
unpacking) minus the device-busy time inside them, per transform
application finished in the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    apps = m["counters"].get("applications", 0)
    if ps is None or not apps:
        return None
    sim = ps.prefixed("rcim.aig_sim.")
    return sum(sp.seconds - ps.busy_s(sp) for sp in sim) / apps * 1e3
