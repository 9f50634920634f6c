"""Host time of characterizing outputs (`Aig.characterize`, the
``rcim.cha.stats`` spans) and persisting them to the cache
(``rcim.cha.persist``), per transform application finished in the
traced window."""

import program_spans


def read(m):
    ps = program_spans.load(m)
    apps = m["counters"].get("applications", 0)
    if ps is None or not apps:
        return None
    return (ps.seconds("rcim.cha.stats") + ps.seconds("rcim.cha.persist")) / apps * 1e3
