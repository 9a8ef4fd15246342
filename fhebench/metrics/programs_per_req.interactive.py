"""Device program executions per request completed in the traced window
(``XLA Modules`` events): the per-request dispatch count that sets the
latency of unbatched serving."""
NEEDS_TRACE = True


def read(win):
    n = win.trace.program_count()
    return n / win.completed if n and win.completed else None
