"""Host time of the fused suite dispatch (the ``rcim.batch.dispatch``
span: laying the variants over the chips and enqueueing the partitioned
program) minus the device-busy time inside it, per `explore_suite` call
of the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    dispatch = ps.named("rcim.batch.dispatch") if calls else []
    if not dispatch:
        return None
    return sum(sp.seconds - ps.busy_s(sp) for sp in dispatch) / calls * 1e3
