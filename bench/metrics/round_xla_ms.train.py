"""Device time per boosting round of every operation that is not one of
the program's Pallas kernels (d-wide passes, sketch, tile-to-node
epilogue, partition gathers and scatters, leaf sums, update, eval loss,
and each fit's binning and packing), from the profiler trace."""
from metrics import kernels as K


def read(run):
    if run.red is None:
        return None
    return 1e3 * run.red.other_s(K.PALLAS) / run.rounds
