"""Host time of the result assembly of `explore_suite` (the
``rcim.explore.assemble`` span: every variant's winner of every circuit
unravelled and named, and the nominal winners materialized) per call of
the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    if not calls:
        return None
    return ps.seconds("rcim.explore.assemble") / calls * 1e3
