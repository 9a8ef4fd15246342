"""Device milliseconds of the jitted NTT and inverse NTT programs
(``repro.core.poly``: ``jit_ntt``, ``jit_intt``) per request completed in
the traced window."""
NEEDS_TRACE = True
PROGRAMS = ("jit_ntt", "jit_intt")


def read(win):
    s = win.trace.program_seconds(lambda name: name in PROGRAMS)
    return 1e3 * s / win.completed if s and win.completed else None
