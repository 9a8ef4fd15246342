"""Share of the HBM-bandwidth roofline reached by the fused AutoU∘KS
program (``repro.kernels.automorphism``, ``jit_auto_ks_pallas``: the Galois
gather and the Pallas evk MAC): least bytes of the window's rotations
(``fhebench.work_bytes.auto_ks_bytes``) at the chip's HBM bandwidth, over
the program's device time. Bandwidth-bound by construction: the peaks
table has no u32 compute peak."""
from fhebench import work_bytes

NEEDS_TRACE = True
PROGRAM = "jit_auto_ks_pallas"


def read(win):
    seconds = win.trace.program_seconds(lambda name: name == PROGRAM)
    nbytes = work_bytes.window_bytes(win.plan_uses, win.deployment.params,
                                     work_bytes.auto_ks_bytes)
    if not seconds or not nbytes:
        return None
    return 100.0 * nbytes / win.peaks["hbm_bytes_per_s"] / seconds
