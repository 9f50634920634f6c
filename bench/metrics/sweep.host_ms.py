"""Host time of one `explore_suite` call: the harness's span around the
call minus the device-busy time inside it, averaged over the calls of
the traced window."""


def read(m):
    red = m["trace"]
    calls = red.spans_named("bench.sweep.call")
    if not calls:
        return None
    host = [(e - s) / 1e9 - red.busy_within(s, e) for s, e in calls]
    return sum(host) / len(host) * 1e3
