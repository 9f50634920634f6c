"""Share of the traced window in which no operation ran on a chip,
averaged over the four chips."""

import common


def read(m):
    return common.idle_pct(m)
