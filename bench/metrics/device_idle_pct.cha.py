"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""

import common


def read(m):
    return common.idle_pct(m)
