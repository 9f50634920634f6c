"""Host time of the fused suite call (the ``rcim.explore.fused`` span
around `evaluate_select_suite`: operand transfer, dispatch, the fetch
of the selection and the lazy grid's assembly) minus the device-busy
time inside it, per `explore_suite` call of the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    if not calls:
        return None
    fused = ps.named("rcim.explore.fused")
    return sum(sp.seconds - ps.busy_s(sp) for sp in fused) / calls * 1e3
