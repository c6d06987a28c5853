"""Device time per boosting round of the histogram tiles kernel
(`hist_tiles_pallas`), summed from the profiler trace."""
from metrics import kernels as K


def read(run):
    if run.red is None:
        return None
    t = run.red.kernel_s(K.HIST)
    return 1e3 * t / run.rounds if t > 0 else None
