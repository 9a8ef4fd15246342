"""Share of the HBM-bandwidth roofline reached by the BConv table product
(``repro.kernels.bconv``, ``jit_bconv_matmul_pallas``): least bytes of the
window's ModUps and ModDowns (``fhebench.work_bytes.bconv_bytes``) at the
chip's HBM bandwidth, over the program's device time."""
from fhebench import work_bytes

NEEDS_TRACE = True
PROGRAM = "jit_bconv_matmul_pallas"


def read(win):
    seconds = win.trace.program_seconds(lambda name: name == PROGRAM)
    nbytes = work_bytes.window_bytes(win.plan_uses, win.deployment.params,
                                     work_bytes.bconv_bytes)
    if not seconds or not nbytes:
        return None
    return 100.0 * nbytes / win.peaks["hbm_bytes_per_s"] / seconds
