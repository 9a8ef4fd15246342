"""HE ops per engine dispatch group in the window: how many requests' ops
the batcher stacks into one dispatch (``ServeMetrics`` counters)."""
NEEDS_TRACE = False


def read(win):
    groups = win.counters["groups_dispatched"]
    return win.counters["ops_executed"] / groups if groups else None
