"""Host time per fit of the quantile fit (`quantize.fit_quantizer`, numpy,
while the chip waits): the program's own host span
``sketchboost.quantiles`` in the profiler trace, clipped to the window.
None where the program records no such span."""
from metrics import scopes as SC


def read(run):
    if run.red is None:
        return None
    ns = SC.span_ns(run.red.host, "quantiles", run.red.lo, run.red.hi)
    return 1e-6 * ns / run.fits if ns > 0 else None
