"""Host time of the suite table (`SuiteTable.from_cha` and
`TopologyTable.from_topologies`, the ``rcim.explore.suite_table`` span)
per `explore_suite` call of the traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    calls = len(ps.named("rcim.explore_suite")) if ps else 0
    if not calls:
        return None
    return ps.seconds("rcim.explore.suite_table") / calls * 1e3
