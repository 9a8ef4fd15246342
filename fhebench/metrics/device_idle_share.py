"""Share of the traced window in which no operation ran on the device."""
NEEDS_TRACE = True


def read(win):
    return 100.0 * win.trace.idle_share()
